"""One LongCat-Flash double layer, the embedding and the untied head, plain
``jax.numpy`` in float32 — the benchmark's own statement of the family
(the repo's is ``fedml_tpu/models/llm/longcat_flash_reference.py``; a test
under ``benchmarks/tests`` holds the two equal at a tiny size). What flows
from layer to layer is the residual stream ``x`` alone. With ``N`` an RMS
norm of its own each, ``H`` heads of ``nope + rope`` score lanes and ``v``
value lanes:

a double layer
1. for ``i`` in 0, 1: ``x <- x + attn_i(N_in_i(x))``; ``u_i = N_post_i(x)``;
   where ``i == 0``, ``m = moe(u_0)``; ``x <- x + mlp_i(u_i)``;
2. ``x <- x + m``.

attention
3. ``c_q = N(u W_qa)``; ``q = sqrt(hidden / q_lora_rank) (c_q W_qb)``
   (LoRA on both);
4. ``[c_kv | k_r] = u W_kva``; ``c_kv <- sqrt(hidden / kv_lora_rank)
   N(c_kv)``; ``[k_nope | v] = c_kv W_kvb`` a head (LoRA on both);
5. rotary embedding (half-split pairs, ``rope_theta``) on ``q``'s rope
   lanes and on the ONE ``k_r``; causal softmax at ``(nope + rope) **
   -0.5``, in blocks of ``Q_BLOCK`` queries so that ``H x T x T`` float32
   scores never exist at once, over values narrower than the scores; then
   ``W_o`` (LoRA).

dense FFN: ``(silu(u W_g) * (u W_u)) W_d``.

expert branch (``n_routed_experts`` FFN experts held here from
``run.held_experts_first`` on, of the published ones, beside
``zero_expert_num`` identity experts)
6. router in float32 at full precision whatever ``mm`` is: ``p =
   softmax(u W_r)`` over FFN and identity experts, chosen = the
   ``moe_topk`` largest of ``p + b``, ``w = routed_scaling_factor
   p[chosen]`` (no renormalisation);
7. ``r = sum_{e chosen and held} w_e (silu(u G_e) * (u U_e)) D_e``;
8. ``r + (sum_{e chosen, identity} w_e) u``.

The weights are the program's leaves, so ``W_qb``'s columns lie a head
``[rope | nope]`` and the rope lanes pair halves (the program's layout; the
published one is ``[nope | rope]`` with neighbours paired, a fixed
permutation of ``W_qb``'s and ``W_kva``'s columns): the split below takes
each part by that order and a score is a sum over lanes, so nothing else
knows. DEPARTURES from the published model are those of the repo's
reference (the scales' placement, the identity experts' ``w u``, no
renormalisation, the selection bias frozen, the untied head, the held
share); the configuration's ``assumed`` lists each.

Every product of the compute type goes through the ``mm`` handed in (the
harness's precision), which takes each weight as it is stored (bfloat16)
and widens it for that product alone: one double layer is 5 GB in float32.
Step 7 computes no expert that no token chose: the held assignments are
sorted by expert into ``ROWS``-row tiles that belong to one expert each,
the tiles that hold an assignment are walked one after another, each
gathering its own rows, multiplying its own expert's three matrices,
weighing its rows and adding each back to its token. The loss walks
the vocabulary in blocks.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.harness.reference import rms_norm, rope

ROWS = 128        # rows of a tile of the expert-sorted buffer
Q_BLOCK = 256     # queries of a block of the attention
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
    """Every double layer is the same computation: one compiled program."""
    return 0


def embed(cfg, top, tokens):
    return top["embed_tokens"][tokens].astype(jnp.float32)


def _softmax_attention(q, k, v, mm):
    """Causal softmax attention, q and k ``[H, T, D]``, v ``[H, T, Dv]``, a
    block of queries at a time against all the keys (masked)."""
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


def _attention(cfg, u, base, lora, name, mm):
    bsz, t, hid = u.shape
    h, nope, r, dv = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                      cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    lat, eps, theta = cfg["kv_lora_rank"], cfg["rms_norm_eps"], \
        cfg["rope_theta"]
    q_scale = math.sqrt(hid / cfg["q_lora_rank"])
    kv_scale = math.sqrt(hid / lat)
    dense = lambda x, leaf: _dense(cfg, x, base, lora, f"{name}/{leaf}", mm)
    heads = lambda z, n: z.reshape(bsz, t, h, n).transpose(0, 2, 1, 3)
    c_q = rms_norm(dense(u, "q_a_proj"), base[f"{name}/q_a_norm/scale"], eps)
    q = heads(q_scale * dense(c_q, "q_b_proj"), r + nope)               # 3
    q_r, q_nope = q[..., :r], q[..., r:]      # the program's lane order
    kv_a = dense(u, "kv_a_proj")                                        # 4
    c_kv = kv_scale * rms_norm(kv_a[..., :lat],
                               base[f"{name}/kv_a_norm/scale"], eps)
    k_r = kv_a[..., None, :, lat:]                            # [B, 1, T, r]
    kv = heads(dense(c_kv, "kv_b_proj"), nope + dv)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_r, k_r = rope(q_r, theta), rope(k_r, theta)                       # 5
    q = jnp.concatenate([q_nope, q_r], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_r, (bsz, h, t, r))], axis=-1)
    o = jnp.stack([_softmax_attention(q[b], k[b], v[b], mm)
                   for b in range(bsz)])
    return dense(o.transpose(0, 2, 1, 3).reshape(bsz, t, h * dv), "o_proj")


def _swiglu(cfg, u, base, lora, name, mm):
    dense = lambda x, leaf: _dense(cfg, x, base, lora, f"{name}/{leaf}", mm)
    return dense(jax.nn.silu(dense(u, "gate_proj")) * dense(u, "up_proj"),
                 "down_proj")


def route(cfg, u, base):
    """Step 6 over ``[S, hidden]``; float32 at full precision whatever the
    harness's ``mm``: ``(chosen [S, k], weights [S, k])``."""
    p = jax.nn.softmax(jnp.matmul(
        u, base["moe/router_weight"].astype(jnp.float32), precision=FULL),
        axis=-1)
    _, chosen = jax.lax.top_k(p + base["moe/router_bias"], cfg["moe_topk"])
    return chosen, cfg["routed_scaling_factor"] * jnp.take_along_axis(
        p, chosen, axis=-1)


def _held_experts(cfg, u, chosen, weights, base, mm):
    """Step 7 over the held assignments sorted by expert; u ``[S,
    hidden]``. An assignment to an expert that is not held (an FFN expert
    held elsewhere, or an identity expert) sorts last and is given no
    row."""
    s, hid = u.shape
    k = chosen.shape[1]
    held, first = cfg["n_routed_experts"], cfg["run"]["held_experts_first"]
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
                     tiles * ROWS)                   # past the end: no row
    # each row's token and weight (0 on a row that holds no assignment)
    row_token = jnp.zeros((tiles * ROWS,), jnp.int32).at[slot].set(
        by_token, mode="drop").reshape(tiles, ROWS)
    row_weight = jnp.zeros((tiles * ROWS,), jnp.float32).at[slot].set(
        weights.reshape(-1)[order], mode="drop").reshape(tiles, ROWS)
    owner = jnp.minimum(jnp.searchsorted(last_tile, jnp.arange(tiles),
                                         side="right"), held - 1)
    gate, up, down = (base[f"moe/experts/{name}_proj"]
                      for name in ("gate", "up", "down"))

    # a tile gathers its rows, weighs them and adds them to their tokens
    # itself, and the backward pass computes it again from its rows: no
    # buffer of every row that could be held ([tiles x ROWS, hidden], 1.26
    # GB in float32 at T4096) is ever made, either way
    @jax.checkpoint
    def tile(index, tokens, w, e):
        x_tile = u[tokens]
        # the expert's own matrices go into the branch, not the stacks
        mats = (gate[e], up[e], down[e])
        return jax.lax.cond(
            index < last_tile[-1],
            lambda g, v, d: w[:, None] * mm(
                jax.nn.silu(mm(x_tile, g)) * mm(x_tile, v), d),
            lambda g, v, d: jnp.zeros_like(x_tile), *mats)

    def step(out, args):
        return out.at[args[1]].add(tile(*args)), None

    out, _ = jax.lax.scan(step, jnp.zeros_like(u),
                          (jnp.arange(tiles), row_token, row_weight, owner))
    return out


def _moe(cfg, u, base, mm):
    bsz, t, hid = u.shape
    flat = u.reshape(bsz * t, hid)
    chosen, weights = route(cfg, flat, base)                            # 6
    routed = _held_experts(cfg, flat, chosen, weights, base, mm)        # 7
    identity = chosen >= cfg["published"]["n_routed_experts"]           # 8
    w_zero = jnp.sum(jnp.where(identity, weights, 0.0), -1, keepdims=True)
    return (routed + w_zero * flat).reshape(bsz, t, hid)


def layer(cfg, i, x, base, lora, mm):
    eps = cfg["rms_norm_eps"]
    for j in (0, 1):                                                    # 1
        x = x + _attention(cfg, rms_norm(x, base[f"input_norm_{j}/scale"],
                                         eps), base, lora, f"attn_{j}", mm)
        u = rms_norm(x, base[f"post_attn_norm_{j}/scale"], eps)
        if j == 0:
            shortcut = _moe(cfg, u, base, mm)
        x = x + _swiglu(cfg, u, base, lora, f"mlp_{j}", mm)
    return x + shortcut                                                 # 2


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
