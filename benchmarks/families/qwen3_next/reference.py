"""One Qwen3-Next layer of each kind, the embedding and the untied head,
plain ``jax.numpy`` in float32 — the benchmark's own statement of the
family (the repo's is ``fedml_tpu/models/llm/qwen3_next_reference.py``; a
test under ``benchmarks/tests`` holds the two equal at a tiny size). What
flows from layer to layer is the residual stream ``x`` alone. Layer ``i``
is full attention where ``(i + 1) % full_attention_interval == 0``, else
Gated DeltaNet; every layer is ``x <- x + mixer(N_in(x))``, ``x <- x +
moe(N_post(x))``, ``N`` the zero-centred RMS norm ``x * rsqrt(mean(x^2) +
eps) * (1 + w)``. With ``u`` the normed stream:

Gated DeltaNet (``hk`` key heads of ``dk``, ``hv`` value heads of ``dv``,
``r = hv / hk``)
1. ``[q | k | v | z] = u W_qkvz`` (LoRA), a key head's columns ``[q_h | k_h
   | r value heads' v | r value heads' z]``; ``[b | a] = u W_ba``, a key
   head's ``[r b | r a]``;
2. ``[q | k | v] <- silu(conv([q | k | v]))``, a depthwise causal
   convolution without bias;
3. ``q, k`` L2-normalised a key head (eps 1e-6), ``q / sqrt(dk)``, each key
   head repeated over its ``r`` value heads; ``beta = sigmoid(b)``, ``g =
   -exp(A_log) softplus(a + dt_bias)``;
4. the delta rule ``S' = exp(g) S``, ``delta = beta (v - S'^T k)``, ``S = S'
   + k delta^T``, ``o = S^T q``: the SEQUENTIAL loop over tokens (a
   ``lax.scan``; not the program's chunked form), walked in blocks of
   ``SCAN_BLOCK`` tokens whose inside the backward pass computes again, so
   that T4096 keeps 64 states and not 4,096;
5. ``RMSNorm(o) * w_n * silu(z)`` a value head, then ``W_out`` (LoRA).

attention
6. ``u W_q`` holds ``[q_h | gate_h]`` a head (LoRA); ``k, v`` (LoRA);
   zero-centred norms of ``q`` and ``k`` a head; rotary embedding on the
   leading ``head_dim x partial_rotary_factor`` lanes;
7. causal softmax at ``head_dim ** -0.5``, each key-value head serving its
   query heads, in blocks of ``Q_BLOCK`` queries so that ``H x T x T``
   float32 scores never exist at once; ``o * sigmoid(gate)``, ``W_o``
   (LoRA).

experts (routed over all the published experts; ``num_experts`` held here
from ``run.held_experts_first`` on)
8. router in float32 at full precision whatever ``mm`` is: ``p = softmax(u
   W_r)``, chosen = the ``num_experts_per_tok`` largest, ``w = p[chosen] /
   sum p[chosen]``;
9. ``r = sum_{e chosen and held} w_e (silu(u G_e) * (u U_e)) D_e``;
10. ``r + sigmoid(u w_sg) * (silu(u S_g) * (u S_u)) S_d``.

DEPARTURES from the published model are those of the repo's reference
(multi-token prediction not built, the L2 norm's eps, the routing weights
kept in float32, the held share); the configuration's ``assumed`` lists
each.

Every product of the compute type goes through the ``mm`` handed in (the
harness's precision); the router and the recurrence itself are float32 at
full precision. Step 9 computes no expert that no token chose: the held
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
SCAN_BLOCK = 64   # tokens of a block of the sequential recurrence
Q_BLOCK = 512     # queries of a block of the attention
L2_EPS = 1e-6
FULL = jax.lax.Precision.HIGHEST


def _dense(cfg, x, base, lora, name, mm):
    y = mm(x, base[f"{name}/kernel"])
    a = lora.get(f"{name}/lora_a")
    if a is not None:
        run = cfg["run"]
        y = y + mm(mm(x, a), lora[f"{name}/lora_b"]) * (
            run["lora_alpha"] / run["lora_rank"])
    return y


def _norm(x, w, eps):
    return rms_norm(x, 1.0 + w, eps)


def layer_kind(cfg, i):
    """Layers of one kind are the same computation: one compiled program a
    kind (Gated DeltaNet 0, attention 1)."""
    return int((i + 1) % cfg["full_attention_interval"] == 0)


def embed(cfg, top, tokens):
    return top["embed_tokens"][tokens].astype(jnp.float32)


def _recurrence(q, k, v, g, beta):
    """Step 4: q, k ``[B, T, H, dk]``, v ``[B, T, H, dv]``, g and beta ``[B,
    T, H]`` -> o ``[B, T, H, dv]``."""
    bsz, t, heads, dk = q.shape
    block = next(n for n in (SCAN_BLOCK, 32, 16, 8, 4, 2, 1) if t % n == 0)

    def token(s, now):
        q_t, k_t, v_t, g_t, b_t = now
        s = jnp.exp(g_t)[..., None, None] * s
        pred = jnp.einsum("bhkv,bhk->bhv", s, k_t, precision=FULL)
        s = s + k_t[..., None] * (b_t[..., None] * (v_t - pred))[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t, precision=FULL)

    @jax.checkpoint
    def tokens_of_a_block(s, blk):
        return jax.lax.scan(token, s, blk)

    by_block = lambda z: jnp.swapaxes(z, 0, 1).reshape(
        t // block, block, *z.shape[:1], *z.shape[2:])
    _, o = jax.lax.scan(
        tokens_of_a_block,
        jnp.zeros((bsz, heads, dk, v.shape[-1]), jnp.float32),
        tuple(by_block(z) for z in (q, k, v, g, beta)))
    return jnp.swapaxes(o.reshape(t, bsz, heads, v.shape[-1]), 0, 1)


def _linear_attention(cfg, u, base, lora, mm):
    bsz, t, _ = u.shape
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    taps, r = cfg["linear_conv_kernel_dim"], hv // hk
    dense = lambda x, name: _dense(cfg, x, base, lora, f"linear_attn/{name}",
                                   mm)
    qkvz = dense(u, "in_proj_qkvz").reshape(bsz, t, hk, 2 * dk + 2 * r * dv)
    ba = dense(u, "in_proj_ba").reshape(bsz, t, hk, 2 * r)              # 1
    q, k = qkvz[..., :dk], qkvz[..., dk:2 * dk]
    v, z = qkvz[..., 2 * dk:2 * dk + r * dv], qkvz[..., 2 * dk + r * dv:]
    mixed = jnp.concatenate([x.reshape(bsz, t, -1) for x in (q, k, v)], -1)
    past = jnp.concatenate(                                             # 2
        [jnp.zeros((bsz, taps - 1, mixed.shape[-1]), mixed.dtype), mixed],
        axis=1)
    kernel = base["linear_attn/conv_kernel"]
    mixed = jax.nn.silu(sum(kernel[j] * past[:, j:j + t] for j in range(taps)))
    q, k, v = jnp.split(mixed, [hk * dk, 2 * hk * dk], axis=-1)
    l2 = lambda x: x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True)
                                     + L2_EPS)
    q = jnp.repeat(l2(q.reshape(bsz, t, hk, dk)) * dk ** -0.5, r, axis=2)  # 3
    k = jnp.repeat(l2(k.reshape(bsz, t, hk, dk)), r, axis=2)
    beta = jax.nn.sigmoid(ba[..., :r].reshape(bsz, t, hv))
    g = -jnp.exp(base["linear_attn/A_log"]) * jax.nn.softplus(
        ba[..., r:].reshape(bsz, t, hv) + base["linear_attn/dt_bias"])
    o = _recurrence(q, k, v.reshape(bsz, t, hv, dv), g, beta)           # 4
    y = rms_norm(o, base["linear_attn/gate_norm_scale"],                # 5
                 cfg["rms_norm_eps"]) * jax.nn.silu(z.reshape(bsz, t, hv, dv))
    return dense(y.reshape(bsz, t, hv * dv), "out_proj")


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
    return out.swapaxes(0, 1).reshape(h, t, d)


def _attention(cfg, u, base, lora, mm):
    bsz, t, _ = u.shape
    h, hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    rot, eps = int(d * cfg["partial_rotary_factor"]), cfg["rms_norm_eps"]
    dense = lambda x, name: _dense(cfg, x, base, lora, f"attn/{name}", mm)
    heads = lambda z, n: z.reshape(bsz, t, n, -1).transpose(0, 2, 1, 3)
    q_gate = heads(dense(u, "q_proj"), h)                               # 6
    q, gate = q_gate[..., :d], q_gate[..., d:]
    q = _norm(q, base["attn/q_norm/weight"], eps)
    k = _norm(heads(dense(u, "k_proj"), hkv), base["attn/k_norm/weight"], eps)
    v = heads(dense(u, "v_proj"), hkv)
    turn = lambda x: jnp.concatenate(
        [rope(x[..., :rot], cfg["rope_theta"]), x[..., rot:]], axis=-1)
    q, k = turn(q), turn(k)
    k, v = (jnp.repeat(x, h // hkv, axis=1) for x in (k, v))
    o = jnp.stack([_softmax_attention(q[b], k[b], v[b], mm)             # 7
                   for b in range(bsz)])
    o = o * jax.nn.sigmoid(gate)
    return dense(o.transpose(0, 2, 1, 3).reshape(bsz, t, h * d), "o_proj")


def route(cfg, u, base):
    """Step 8 over ``[S, hidden]``; float32 at full precision whatever the
    harness's ``mm``: ``(chosen [S, k], weights [S, k])``."""
    p = jax.nn.softmax(jnp.matmul(
        u, base["moe/router_weight"].astype(jnp.float32), precision=FULL),
        axis=-1)
    kept, chosen = jax.lax.top_k(p, cfg["num_experts_per_tok"])
    return chosen, kept / jnp.sum(kept, -1, keepdims=True)


def _held_experts(cfg, u, chosen, weights, base, mm):
    """Step 9 over the held assignments sorted by expert; u ``[S,
    hidden]``. An assignment to an expert that is not held sorts last and
    is given no row."""
    s, hid = u.shape
    k = chosen.shape[1]
    held, first = cfg["num_experts"], cfg["run"]["held_experts_first"]
    local = chosen.reshape(-1) - first
    local = jnp.where((local >= 0) & (local < held), local, held)       # [A]
    token = jnp.repeat(jnp.arange(s), k)
    counts = jnp.zeros((held + 1,), jnp.int32).at[local].add(1)[:held]
    tiles_of = (counts + ROWS - 1) // ROWS
    tiles = s * min(k, held) // ROWS + held         # at least sum(tiles_of)
    last_tile = jnp.cumsum(tiles_of)
    order = jnp.argsort(local, stable=True)
    by_expert, by_token = local[order], token[order]
    here = by_expert < held
    safe = jnp.minimum(by_expert, held - 1)
    rank = jnp.arange(s * k) - (jnp.cumsum(counts) - counts)[safe]
    slot = jnp.where(here, (last_tile - tiles_of)[safe] * ROWS + rank,
                     tiles * ROWS)                   # past the end: dropped
    owner = jnp.minimum(jnp.searchsorted(last_tile, jnp.arange(tiles),
                                         side="right"), held - 1)
    xs = jnp.zeros((tiles * ROWS, hid), u.dtype).at[slot].set(
        u[by_token], mode="drop").reshape(tiles, ROWS, hid)
    gate, up, down = (base[f"moe/experts/{name}_proj"]
                      for name in ("gate", "up", "down"))

    # the backward pass computes a tile again from its rows: kept as
    # residuals, each tile's own copy of its expert's matrices would be
    # gigabytes a layer
    @jax.checkpoint
    def tile(args):
        index, x_tile, e = args
        return jax.lax.cond(
            index < last_tile[-1],
            lambda: mm(jax.nn.silu(mm(x_tile, gate[e])) * mm(x_tile, up[e]),
                       down[e]),
            lambda: jnp.zeros_like(x_tile))

    out = jax.lax.map(tile, (jnp.arange(tiles), xs, owner)).reshape(
        tiles * ROWS, hid)
    mine = jnp.where(here[:, None], out[jnp.minimum(slot, tiles * ROWS - 1)],
                     0.0) * weights.reshape(-1)[order][:, None]
    return jnp.zeros_like(u).at[by_token].add(mine)


def _moe(cfg, u, base, lora, mm):
    bsz, t, hid = u.shape
    flat = u.reshape(bsz * t, hid)
    dense = lambda x, name: _dense(cfg, x, base, lora, f"moe/{name}", mm)
    chosen, weights = route(cfg, flat, base)                            # 8
    routed = _held_experts(cfg, flat, chosen, weights, base, mm)        # 9
    shared = dense(jax.nn.silu(dense(flat, "shared/gate_proj"))         # 10
                   * dense(flat, "shared/up_proj"), "shared/down_proj")
    out = routed + jax.nn.sigmoid(dense(flat, "shared_gate")) * shared
    return out.reshape(bsz, t, hid)


def layer(cfg, i, x, base, lora, mm):
    eps = cfg["rms_norm_eps"]
    mixer = (_linear_attention, _attention)[layer_kind(cfg, i)]
    x = x + mixer(cfg, _norm(x, base["input_norm/weight"], eps), base, lora,
                  mm)
    return x + _moe(cfg, _norm(x, base["post_attn_norm/weight"], eps), base,
                    lora, mm)


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
