"""One Nemotron-H layer of each kind, the embedding and the untied head,
plain ``jax.numpy`` in float32 — the benchmark's own statement of the
family (the repo's is ``fedml_tpu/models/llm/nemotron_h_reference.py``; a
test under ``benchmarks/tests`` holds the two equal at a tiny size). What
flows from layer to layer is the residual stream ``x`` alone. Every layer
is ``x <- x + mixer(RMSNorm(x))``; letter ``i`` of
``hybrid_override_pattern`` says which mixer, with ``u = RMSNorm(x)``:

``M`` (Mamba-2; ``H`` heads of ``P``, ``G`` groups of state ``N``, ``d = H P``)
1. ``[z | xBC | dt] = u W_in`` (LoRA), widths ``d | d + 2 G N | H``;
2. ``xBC <- silu(conv(xBC) + b)``, a depthwise causal convolution of
   ``conv_kernel`` taps;
3. ``x [T, H, P]``, ``B``, ``C [T, G, N]``; ``dt <- softplus(dt + dt_bias)``,
   ``a = -exp(A_log)``;
4. ``h_t = exp(dt_t a) h_{t-1} + dt_t x_t (x) B_t``, ``y_t = C_t h_t + D x_t``:
   the SEQUENTIAL loop over tokens (a ``lax.scan``; not the program's
   chunked form), walked in blocks of tokens whose inside the backward pass
   computes again, so that T2048 keeps 32 states and not 2,048;
5. ``y <- GroupRMSNorm(y * silu(z)) * w`` over ``G`` groups of channels;
6. ``y W_out`` (LoRA).

``*``: ``q, k, v = u W_q, u W_k, u W_v`` (LoRA, no bias, NO rotary
embedding), causal softmax at ``1/sqrt(D)``, ``W_o`` (LoRA).

``E`` (routed over all the published experts; ``n_routed_experts`` held
here from ``run.held_experts_first`` on)
7. router in float32 at full precision whatever ``mm`` is: ``s = sigmoid(u
   W_g)``, chosen = the ``num_experts_per_tok`` largest of ``s + b_sel``,
   ``w = s[chosen] / (sum s[chosen] + 1e-20) * routed_scaling_factor``;
8. ``l = u W_fc1``; ``r = sum_{e chosen and held} w_e relu(l U_e)^2 V_e``;
9. ``r W_fc2 + relu(u S_up)^2 S_down``.

DEPARTURES from the published model are those of the repo's reference
(multi-token prediction not built, no rotary embedding, the gate before the
group norm, ``dt`` not clamped, the held share); the configuration's
``assumed`` lists each.

Every product of the compute type goes through the ``mm`` handed in (the
harness's precision); the recurrence itself is elementwise float32. Step 8
computes no expert that no token chose: the held assignments are sorted by
expert into a buffer whose ``ROWS``-row tiles belong to one expert each,
the tiles that hold an assignment are walked one after another (each
multiplies its own expert's two matrices), and each assignment reads its
own row back. The loss walks the vocabulary in blocks.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.harness.reference import attention, rms_norm

ROWS = 128        # rows of a tile of the expert-sorted buffer
SCAN_BLOCK = 64   # tokens of a block of the sequential recurrence
FULL = jax.lax.Precision.HIGHEST
KINDS = "M*E"


def _dense(cfg, x, base, lora, name, mm):
    y = mm(x, base[f"{name}/kernel"])
    a = lora.get(f"{name}/lora_a")
    if a is not None:
        run = cfg["run"]
        y = y + mm(mm(x, a), lora[f"{name}/lora_b"]) * (
            run["lora_alpha"] / run["lora_rank"])
    return y


def _relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def layer_kind(cfg, i):
    """Layers of one letter are the same computation: one compiled program
    a kind."""
    return KINDS.index(cfg["hybrid_override_pattern"][i])


def embed(cfg, top, tokens):
    return top["embed_tokens"][tokens].astype(jnp.float32)


def _recurrence(x, dt, a, b, c):
    """Step 4 without the skip: x ``[B, T, H, P]``, dt ``[B, T, H]``, a
    ``[H]``, b and c ``[B, T, H, N]`` (already one a head)."""
    bsz, t, heads, p = x.shape
    block = next(n for n in (SCAN_BLOCK, 32, 16, 8, 4, 2, 1) if t % n == 0)

    def token(h, now):
        x_t, dt_t, b_t, c_t = now
        h = (jnp.exp(dt_t * a)[..., None, None] * h
             + (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :])
        return h, jnp.sum(h * c_t[..., None, :], axis=-1)

    @jax.checkpoint
    def tokens_of_a_block(h, blk):
        return jax.lax.scan(token, h, blk)

    by_block = lambda z: jnp.swapaxes(z, 0, 1).reshape(
        t // block, block, *z.shape[:1], *z.shape[2:])
    _, y = jax.lax.scan(
        tokens_of_a_block,
        jnp.zeros((bsz, heads, p, b.shape[-1]), jnp.float32),
        tuple(by_block(z) for z in (x, dt, b, c)))
    return jnp.swapaxes(y.reshape(t, bsz, heads, p), 0, 1)


def _mamba(cfg, u, base, lora, mm):
    bsz, t, _ = u.shape
    h, p, g, n = (cfg["mamba_num_heads"], cfg["mamba_head_dim"],
                  cfg["n_groups"], cfg["ssm_state_size"])
    d, taps = h * p, cfg["conv_kernel"]
    conv = d + 2 * g * n
    zxbcdt = _dense(cfg, u, base, lora, "mamba/in_proj", mm)            # 1
    z, xbc, dt = jnp.split(zxbcdt, [d, d + conv], axis=-1)
    past = jnp.concatenate(                                             # 2
        [jnp.zeros((bsz, taps - 1, conv), xbc.dtype), xbc], axis=1)
    xbc = jax.nn.silu(base["mamba/conv_bias"] + sum(
        base["mamba/conv_kernel"][j] * past[:, j:j + t]
        for j in range(taps)))
    x, b, c = jnp.split(xbc, [d, d + g * n], axis=-1)                   # 3
    x = x.reshape(bsz, t, h, p)
    per_head = lambda m: jnp.repeat(m.reshape(bsz, t, g, n), h // g, axis=2)
    dt = jax.nn.softplus(dt + base["mamba/dt_bias"])
    y = _recurrence(x, dt, -jnp.exp(base["mamba/A_log"]),               # 4
                    per_head(b), per_head(c))
    y = y + base["mamba/D"][:, None] * x
    gated = (y.reshape(bsz, t, d) * jax.nn.silu(z)).reshape(            # 5
        bsz, t, g, d // g)
    normed = gated * jax.lax.rsqrt(
        jnp.mean(gated * gated, -1, keepdims=True)
        + cfg["layer_norm_epsilon"])
    y = normed.reshape(bsz, t, d) * base["mamba/gate_norm_scale"]
    return _dense(cfg, y, base, lora, "mamba/out_proj", mm)             # 6


def _attention(cfg, u, base, lora, mm):
    bsz, t, _ = u.shape
    hq, hk, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    dense = lambda x, name: _dense(cfg, x, base, lora, f"attn/{name}", mm)
    split = lambda z, n: z.reshape(bsz, t, n, d).transpose(0, 2, 1, 3)
    q = split(dense(u, "q_proj"), hq)
    k, v = (jnp.repeat(split(dense(u, name), hk), hq // hk, axis=1)
            for name in ("k_proj", "v_proj"))
    flat = lambda z: z.reshape(bsz * hq, t, d)
    o = attention(flat(q), flat(k), flat(v), mm)
    o = o.reshape(bsz, hq, t, d).transpose(0, 2, 1, 3).reshape(bsz, t, hq * d)
    return dense(o, "o_proj")


def route(cfg, u, base):
    """Step 7 over ``[S, hidden]``; float32 at full precision whatever the
    harness's ``mm``: ``(chosen [S, k], weights [S, k])``."""
    s = jax.nn.sigmoid(jnp.matmul(
        u, base["moe/router_weight"].astype(jnp.float32), precision=FULL))
    _, chosen = jax.lax.top_k(s + base["moe/router_bias"],
                              cfg["num_experts_per_tok"])
    kept = jnp.take_along_axis(s, chosen, axis=-1)
    return chosen, kept / (jnp.sum(kept, -1, keepdims=True) + 1e-20) \
        * cfg["routed_scaling_factor"]


def _held_experts(cfg, latent, chosen, weights, base, mm):
    """Step 8 over the held assignments sorted by expert; latent ``[S,
    latent]``. An assignment to an expert that is not held sorts last and
    is given no row."""
    s, lat = latent.shape
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
                     tiles * ROWS)                   # past the end: dropped
    owner = jnp.minimum(jnp.searchsorted(last_tile, jnp.arange(tiles),
                                         side="right"), held - 1)
    xs = jnp.zeros((tiles * ROWS, lat), latent.dtype).at[slot].set(
        latent[by_token], mode="drop").reshape(tiles, ROWS, lat)
    up, down = base["moe/experts/up_proj"], base["moe/experts/down_proj"]

    # the backward pass computes a tile again from its rows: kept as
    # residuals, each tile's own copy of its expert's matrices would be
    # 5 GB a layer
    @jax.checkpoint
    def tile(args):
        index, x_tile, e = args
        return jax.lax.cond(
            index < last_tile[-1],
            lambda: mm(_relu2(mm(x_tile, up[e])), down[e]),
            lambda: jnp.zeros_like(x_tile))

    out = jax.lax.map(tile, (jnp.arange(tiles), xs, owner)).reshape(
        tiles * ROWS, lat)
    mine = jnp.where(here[:, None], out[jnp.minimum(slot, tiles * ROWS - 1)],
                     0.0) * weights.reshape(-1)[order][:, None]
    return jnp.zeros_like(latent).at[by_token].add(mine)


def _moe(cfg, u, base, lora, mm):
    bsz, t, hid = u.shape
    flat = u.reshape(bsz * t, hid)
    chosen, weights = route(cfg, flat, base)
    latent = _dense(cfg, flat, base, lora, "moe/latent_in", mm)
    r = _held_experts(cfg, latent, chosen, weights, base, mm)           # 8
    out = _dense(cfg, r, base, lora, "moe/latent_out", mm) + _dense(   # 9
        cfg, _relu2(_dense(cfg, flat, base, lora, "moe/shared/up_proj", mm)),
        base, lora, "moe/shared/down_proj", mm)
    return out.reshape(bsz, t, hid)


def layer(cfg, i, x, base, lora, mm):
    u = rms_norm(x, base["input_norm/scale"], cfg["layer_norm_epsilon"])
    mixer = (_mamba, _attention, _moe)[layer_kind(cfg, i)]
    return x + mixer(cfg, u, base, lora, mm)


def head(cfg, x, top, targets, mm):
    """Mean next-token cross-entropy against the untied head, the
    vocabulary walked in blocks: a running log-sum-exp and the target's
    own logit."""
    x = rms_norm(x, top["final_norm/scale"], cfg["layer_norm_epsilon"])
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
