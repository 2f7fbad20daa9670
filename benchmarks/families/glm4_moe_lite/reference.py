"""One GLM-4.7-Flash layer of each kind, the embedding and the untied head,
plain ``jax.numpy`` in float32 — the benchmark's own statement of the
family (the repo's is ``fedml_tpu/models/llm/glm_moe_lite_reference.py``; a
test under ``benchmarks/tests`` holds the two equal at a tiny size). What
flows from layer to layer is the residual stream ``x`` alone. Every layer
is ``x <- x + attn(RMSNorm_in(x))``, ``x <- x + ffn(RMSNorm_post(x))``; with
``u`` the normed stream, ``H`` heads of ``nope + rope`` score lanes and
``v`` value lanes:

attention
1. ``c_q = RMSNorm(u W_qa)``; ``q = c_q W_qb`` (LoRA on both);
2. ``[c_kv | k_r] = u W_kva``; ``c_kv <- RMSNorm(c_kv)``; ``[k_nope | v] =
   c_kv W_kvb`` a head (LoRA on both);
3. rotary embedding (half-split pairs, ``rope_theta``) on ``q``'s ``rope``
   lanes and on the ONE ``k_r``, which every head's key then carries;
4. causal softmax at ``(nope + rope) ** -0.5``, in blocks of ``Q_BLOCK``
   queries so that ``H x T x T`` float32 scores never exist at once, then
   ``W_o`` (LoRA).

dense FFN (layers before ``first_k_dense_replace``)
5. ``(silu(u W_g) * (u W_u)) W_d``.

expert FFN (the other layers)
6. router in float32 at full precision whatever ``mm`` is: ``s = sigmoid(u
   W_r)``, chosen = the ``num_experts_per_tok`` largest of ``s + b_sel``,
   ``w = s[chosen] / (sum s[chosen] + 1e-20) * routed_scaling_factor``;
7. ``r = sum_{e chosen} w_e (silu(u G_e) * (u U_e)) D_e``;
8. ``r + (silu(u S_g) * (u S_u)) S_d``.

The weights are the program's leaves, so ``W_qb``'s columns lie a head
``[rope | nope]`` (the program's lane order; the published one is ``[nope |
rope]``, a fixed permutation of those columns): the split below takes each
part by that order and a score is a sum over lanes, so nothing else knows.
DEPARTURES from the published model are those of the repo's reference
(multi-token prediction not built, half-split rotary pairs, both latent
norms at ``rms_norm_eps``, the shared expert unweighted, the selection
bias picks and does not weigh); the configuration's ``assumed`` lists each.

Every product of the compute type goes through the ``mm`` handed in (the
harness's precision). Step 7 computes no expert that no token chose: the
assignments are sorted by expert into a buffer whose ``ROWS``-row tiles
belong to one expert each, the tiles that hold an assignment are walked
one after another (each multiplies its own expert's three matrices), and
each assignment reads its own row back. The loss walks the vocabulary in
blocks.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.harness.reference import rms_norm, rope

ROWS = 128        # rows of a tile of the expert-sorted buffer
Q_BLOCK = 512     # queries of a block of the attention
FULL = jax.lax.Precision.HIGHEST


def _dense(cfg, x, base, lora, name, mm):
    y = mm(x, base[f"{name}/kernel"])
    a = lora.get(f"{name}/lora_a")
    if a is not None:
        run = cfg["run"]
        y = y + mm(mm(x, a), lora[f"{name}/lora_b"]) * (
            run["lora_alpha"] / run["lora_rank"])
    return y


def layer_kind(cfg, i):
    """Layers of one kind are the same computation: one compiled program a
    kind (the dense layer's, the expert layers')."""
    return int(i >= cfg["first_k_dense_replace"])


def embed(cfg, top, tokens):
    return top["embed_tokens"][tokens].astype(jnp.float32)


def _softmax_attention(q, k, v, mm):
    """Causal softmax attention over ``[H, T, D]``, a block of queries at a
    time against all the keys (masked)."""
    h, t, d = q.shape
    block = next(n for n in (Q_BLOCK, 256, 128, 64, 32, 16, 8, 4, 2, 1)
                 if t % n == 0)
    kt = jnp.swapaxes(k, -1, -2)

    @jax.checkpoint
    def some_queries(args):
        start, qb = args
        s = mm(qb, kt) * (d ** -0.5)                        # [H, block, T]
        seen = (start + jnp.arange(block))[:, None] >= jnp.arange(t)
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return mm(p, v)

    blocks = q.reshape(h, t // block, block, d).swapaxes(0, 1)
    out = jax.lax.map(some_queries,
                      (jnp.arange(t // block) * block, blocks))
    return out.swapaxes(0, 1).reshape(h, t, v.shape[-1])


def _attention(cfg, u, base, lora, mm):
    bsz, t, _ = u.shape
    h, nope, r, dv = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                      cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    lat, eps, theta = cfg["kv_lora_rank"], cfg["rms_norm_eps"], \
        cfg["rope_theta"]
    dense = lambda x, name: _dense(cfg, x, base, lora, f"attn/{name}", mm)
    heads = lambda z, n: z.reshape(bsz, t, h, n).transpose(0, 2, 1, 3)
    c_q = rms_norm(dense(u, "q_a_proj"), base["attn/q_a_norm/scale"], eps)  # 1
    q = heads(dense(c_q, "q_b_proj"), r + nope)
    q_r, q_nope = q[..., :r], q[..., r:]      # the program's lane order
    kv_a = dense(u, "kv_a_proj")                                        # 2
    c_kv = rms_norm(kv_a[..., :lat], base["attn/kv_a_norm/scale"], eps)
    k_r = kv_a[..., None, :, lat:]                            # [B, 1, T, r]
    kv = heads(dense(c_kv, "kv_b_proj"), nope + dv)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_r, k_r = rope(q_r, theta), rope(k_r, theta)                       # 3
    q = jnp.concatenate([q_nope, q_r], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_r, (bsz, h, t, r))], axis=-1)
    o = jnp.stack([_softmax_attention(q[b], k[b], v[b], mm)             # 4
                   for b in range(bsz)])
    return dense(o.transpose(0, 2, 1, 3).reshape(bsz, t, h * dv), "o_proj")


def _swiglu(cfg, u, base, lora, name, mm):
    dense = lambda x, leaf: _dense(cfg, x, base, lora, f"{name}/{leaf}", mm)
    return dense(jax.nn.silu(dense(u, "gate_proj")) * dense(u, "up_proj"),
                 "down_proj")


def route(cfg, u, base):
    """Step 6 over ``[S, hidden]``; float32 at full precision whatever the
    harness's ``mm``: ``(chosen [S, k], weights [S, k])``."""
    s = jax.nn.sigmoid(jnp.matmul(
        u, base["moe/router_weight"].astype(jnp.float32), precision=FULL))
    _, chosen = jax.lax.top_k(s + base["moe/router_bias"],
                              cfg["num_experts_per_tok"])
    kept = jnp.take_along_axis(s, chosen, axis=-1)
    return chosen, kept / (jnp.sum(kept, -1, keepdims=True) + 1e-20) \
        * cfg["routed_scaling_factor"]


def _experts(cfg, u, chosen, weights, base, mm):
    """Step 7 over the assignments sorted by expert; u ``[S, hidden]``."""
    s, hid = u.shape
    k, e = chosen.shape[1], cfg["n_routed_experts"]
    expert = chosen.reshape(-1)                                          # [A]
    token = jnp.repeat(jnp.arange(s), k)
    counts = jnp.zeros((e,), jnp.int32).at[expert].add(1)
    tiles_of = (counts + ROWS - 1) // ROWS
    tiles = s * k // ROWS + e                      # at least sum(tiles_of)
    last_tile = jnp.cumsum(tiles_of)
    order = jnp.argsort(expert, stable=True)
    by_expert, by_token = expert[order], token[order]
    rank = jnp.arange(s * k) - (jnp.cumsum(counts) - counts)[by_expert]
    slot = (last_tile - tiles_of)[by_expert] * ROWS + rank
    owner = jnp.minimum(jnp.searchsorted(last_tile, jnp.arange(tiles),
                                         side="right"), e - 1)
    xs = jnp.zeros((tiles * ROWS, hid), u.dtype).at[slot].set(
        u[by_token]).reshape(tiles, ROWS, hid)
    gate, up, down = (base[f"moe/experts/{name}_proj"]
                      for name in ("gate", "up", "down"))

    # the backward pass computes a tile again from its rows: kept as
    # residuals, each tile's own copy of its expert's matrices would be
    # 7 GB a layer
    @jax.checkpoint
    def tile(args):
        index, x_tile, i = args
        return jax.lax.cond(
            index < last_tile[-1],
            lambda: mm(jax.nn.silu(mm(x_tile, gate[i])) * mm(x_tile, up[i]),
                       down[i]),
            lambda: jnp.zeros_like(x_tile))

    out = jax.lax.map(tile, (jnp.arange(tiles), xs, owner)).reshape(
        tiles * ROWS, hid)
    mine = out[slot] * weights.reshape(-1)[order][:, None]
    return jnp.zeros_like(u).at[by_token].add(mine)


def _moe(cfg, u, base, lora, mm):
    bsz, t, hid = u.shape
    flat = u.reshape(bsz * t, hid)
    chosen, weights = route(cfg, flat, base)
    out = _experts(cfg, flat, chosen, weights, base, mm) \
        + _swiglu(cfg, flat, base, lora, "moe/shared", mm)               # 8
    return out.reshape(bsz, t, hid)


def layer(cfg, i, x, base, lora, mm):
    eps = cfg["rms_norm_eps"]
    x = x + _attention(cfg, rms_norm(x, base["input_norm/scale"], eps),
                       base, lora, mm)
    u = rms_norm(x, base["post_attn_norm/scale"], eps)
    if layer_kind(cfg, i) == 0:
        return x + _swiglu(cfg, u, base, lora, "mlp", mm)                # 5
    return x + _moe(cfg, u, base, lora, mm)


def head(cfg, x, top, targets, mm):
    """Mean next-token cross-entropy against the untied head, the
    vocabulary walked in blocks: a running log-sum-exp and the target's
    own logit."""
    x = rms_norm(x, top["final_norm/scale"], cfg["rms_norm_eps"])
    w = top["lm_head"]
    vocab = w.shape[1]
    blocks = next(n for n in (8, 4, 2, 1) if vocab % n == 0)
    size = vocab // blocks

    @jax.checkpoint
    def block(x, start):
        logits = mm(x, jax.lax.dynamic_slice_in_dim(w, start, size, axis=1))
        at = targets - start
        own = jnp.take_along_axis(
            logits, jnp.clip(at, 0, size - 1)[..., None], -1)[..., 0]
        return (jax.nn.logsumexp(logits, axis=-1),
                jnp.where((at >= 0) & (at < size), own, 0.0))

    lse = jnp.full(targets.shape, -jnp.inf, jnp.float32)
    own = jnp.zeros(targets.shape, jnp.float32)
    for n in range(blocks):
        block_lse, block_own = block(x, n * size)
        lse, own = jnp.logaddexp(lse, block_lse), own + block_own
    return jnp.mean(lse - own)
