"""One ZAYA1 layer, the embedding and the tied head, plain ``jax.numpy`` in
float32 — the benchmark's own statement of steps 1-9 (the repo's is
``fedml_tpu/models/llm/zaya_reference.py``; a test under ``benchmarks/
tests`` holds the two equal at a tiny size). What flows from layer to
layer is the pair ``(x, s)``: the residual stream and the router's state.

With ``h = RMSNorm(x)`` before each sublayer and a residual add after it;
``Hq`` query and ``Hk`` key-value heads of size ``D``, ``g = Hq / Hk``:

1. ``q~ = h Wq``, ``k~ = h Wk`` (LoRA on both), no bias;
2. on ``c = concat(q~, k~)``: a depthwise causal convolution of
   ``cca_time0`` taps with bias, then one of ``cca_time1`` taps whose tap
   matrices are block-diagonal over the ``Hq + Hk`` heads, with bias;
3. ``mq = (q~ + repeat(k~, g)) / 2``, ``mk`` its mean over each group,
   ``q = qc + mq``, ``k = kc + mk``;
4. ``v = concat(h Wv, shift(h) Wv')`` over the key-value heads;
5. ``q <- sqrt(D) q / |q|``, ``k <- tau_head sqrt(D) k / |k|``;
6. rope (halves convention) on the first ``partial_rotary_factor * D`` of
   each head; 7. causal softmax attention at ``1/sqrt(D)``, then ``Wo``;
8. router in float32 at full precision whatever ``mm`` is (the
   configuration states it so in every compute type): ``r = h Wd``,
   ``s_l = r + gamma_l s_{l-1}``, ``z = W3 gelu(W2 gelu(W1 RMSNorm(s_l)))``,
   ``p = softmax(z)``, ``e = argmax p``;
9. ``y = p_e Wdown_e (silu(h Wgate_e) * (h Wup_e))``, no token dropped.

POSSIBLE DEPARTURES from the released model: steps 2 (grouping, biases),
3, 4, 5 and 8 (the MLP's depth, gelu in its tanh form, the carried state's
form; no balancing bias, no skip expert) are not fixed by the public
``config.json`` and are written from arXiv:2510.04476 and arXiv:2511.17127
without the released code; the configuration's ``assumed`` lists each.

Every product of the compute type goes through the ``mm`` handed in (the
harness's precision). Step 9 computes no expert a token was not sent to:
the tokens are sorted by expert into a buffer whose ``ROWS``-row tiles
belong to one expert each, every tile multiplies its own expert's matrix
(gathered), and each token reads its own row back. The loss walks the
vocabulary in blocks, so that ``[T, V]`` logits never stand whole beside
the 24 layers this reference keeps on the chip.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.harness.reference import attention, rms_norm, rope

L2_EPS = 1e-6   # under the root of a head's squared norm (step 5)
ROWS = 64       # rows of a tile of the expert-sorted buffer
FULL = jax.lax.Precision.HIGHEST


def _dense(cfg, x, base, lora, name, mm):
    y = mm(x, base[f"{name}/kernel"])
    a = lora.get(f"{name}/lora_a")
    if a is not None:
        run = cfg["run"]
        y = y + mm(mm(x, a), lora[f"{name}/lora_b"]) * (
            run["lora_alpha"] / run["lora_rank"])
    return y


def _shift(x, by=1):
    """``y[:, t] = x[:, t - by]``, zeros before ``t = 0``; x ``[B, T, ...]``."""
    if by == 0:
        return x
    return jnp.concatenate([jnp.zeros_like(x[:, :by]), x[:, :-by]], axis=1)


def layer_kind(cfg, i):
    """Every layer is the same computation: one compiled program."""
    return 0


def embed(cfg, top, tokens):
    x = top["embed_tokens"][tokens].astype(jnp.float32)
    return x, jnp.zeros(x.shape[:-1] + (cfg["router_hidden_size"],),
                        jnp.float32)


def _attention(cfg, h, base, lora, mm):
    b, t, _ = h.shape
    hq, hk, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    g, heads = hq // hk, hq + hk
    dense = lambda x, name: _dense(cfg, x, base, lora, f"attn/{name}", mm)
    q_lat, k_lat = dense(h, "q_proj"), dense(h, "k_proj")            # 1
    c = jnp.concatenate([q_lat, k_lat], -1)                           # 2
    c1 = base["attn/conv0_bias"] + sum(
        base["attn/conv0_kernel"][j] * _shift(c, j)
        for j in range(cfg["cca_time0"]))
    c1 = c1.reshape(b, t, heads, d)
    c2 = base["attn/conv1_bias"].reshape(heads, 1, d) + sum(
        mm(_shift(c1, j).transpose(0, 2, 1, 3),              # [B, heads, T, D]
           base["attn/conv1_kernel"][j])
        for j in range(cfg["cca_time1"]))
    split = lambda z, n: z.reshape(b, t, n, d).transpose(0, 2, 1, 3)
    q4, k4 = split(q_lat, hq), split(k_lat, hk)                       # 3
    mq = (q4 + jnp.repeat(k4, g, axis=1)) / 2
    mk = mq.reshape(b, hk, g, t, d).mean(2)
    q, k = c2[:, :hq] + mq, c2[:, hq:] + mk
    v = jnp.concatenate([split(dense(h, "v_proj"), hk // 2),          # 4
                         split(dense(_shift(h), "v_prev_proj"), hk // 2)], 1)
    unit = lambda z: math.sqrt(d) * z / jnp.sqrt(                     # 5
        jnp.sum(z * z, -1, keepdims=True) + L2_EPS)
    q = unit(q)
    k = unit(k) * base["attn/k_temp"][:, None, None]
    rot = int(d * cfg["rope_parameters"]["hybrid"]["partial_rotary_factor"])
    theta = float(cfg["rope_parameters"]["hybrid"]["rope_theta"])
    turn = lambda z: jnp.concatenate(                                 # 6
        [rope(z[..., :rot], theta), z[..., rot:]], -1)
    q, k = turn(q), turn(k)
    k, v = (jnp.repeat(z, g, axis=1) for z in (k, v))                 # 7
    flat = lambda z: z.reshape(b * hq, t, d)
    o = attention(flat(q), flat(k), flat(v), mm)
    o = o.reshape(b, hq, t, d).transpose(0, 2, 1, 3).reshape(b, t, hq * d)
    return dense(o, "o_proj")


def _router(cfg, h, s, base):
    """Step 8; float32 at full precision whatever the harness's ``mm``."""
    p = lambda name: base[f"moe/router_mlp/{name}"]
    full = lambda a, w: jnp.matmul(a, w.astype(jnp.float32), precision=FULL)
    s = full(h, p("down")) + p("gamma") * s
    z = full(jax.nn.gelu(full(jax.nn.gelu(full(
        rms_norm(s, p("norm_scale"), cfg["rms_norm_eps"]), p("w1"))),
        p("w2"))), p("w3"))
    return jax.nn.softmax(z, axis=-1), s


def _experts(cfg, h, probs, base, mm):
    """Step 9 over the tokens sorted by expert; h ``[S, hidden]``."""
    n, hid = h.shape
    e = cfg["num_experts"]
    chosen = jnp.argmax(probs, -1)
    p_e = jnp.take_along_axis(probs, chosen[:, None], -1)
    counts = jnp.zeros((e,), jnp.int32).at[chosen].add(1)
    tiles_of = (counts + ROWS - 1) // ROWS
    tiles = n // ROWS + e                     # at least sum(tiles_of)
    last_tile = jnp.cumsum(tiles_of)
    order = jnp.argsort(chosen, stable=True)
    by_expert = chosen[order]
    rank = jnp.arange(n) - (jnp.cumsum(counts) - counts)[by_expert]
    slot = (last_tile - tiles_of)[by_expert] * ROWS + rank
    owner = jnp.minimum(jnp.searchsorted(last_tile, jnp.arange(tiles),
                                         side="right"), e - 1)
    xs = jnp.zeros((tiles * ROWS, hid), h.dtype).at[slot].set(h[order])
    xs = xs.reshape(tiles, ROWS, hid)
    w = lambda name: base[f"moe/experts/{name}"][owner]
    act = jax.nn.silu(mm(xs, w("gate_proj"))) * mm(xs, w("up_proj"))
    out = mm(act, w("down_proj")).reshape(tiles * ROWS, hid)
    return jnp.zeros_like(h).at[order].set(out[slot]) * p_e


def layer(cfg, i, carry, base, lora, mm):
    x, s = carry
    b, t, hid = x.shape
    h = rms_norm(x, base["input_norm/scale"], cfg["rms_norm_eps"])
    x = x + _attention(cfg, h, base, lora, mm)
    h = rms_norm(x, base["post_attn_norm/scale"], cfg["rms_norm_eps"])
    probs, s = _router(cfg, h, s, base)
    y = _experts(cfg, h.reshape(b * t, hid),
                 probs.reshape(b * t, cfg["num_experts"]), base, mm)
    return x + y.reshape(b, t, hid), s


def head(cfg, carry, top, targets, mm):
    """Mean next-token cross-entropy against the tied embedding, the
    vocabulary walked in blocks: a running log-sum-exp and the target's
    own logit."""
    x, _ = carry
    x = rms_norm(x, top["final_norm/scale"], cfg["rms_norm_eps"])
    emb = top["embed_tokens"]
    vocab = emb.shape[0]
    blocks = next(n for n in (8, 4, 2, 1) if vocab % n == 0)
    size = vocab // blocks

    @jax.checkpoint
    def block(x, start):
        rows = jax.lax.dynamic_slice_in_dim(emb, start, size, axis=0)
        logits = mm(x, jnp.swapaxes(rows, 0, 1))
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
