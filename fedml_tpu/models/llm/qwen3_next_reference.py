"""The plain float32 statement of ``qwen3_next.py``: forward, loss and
(through ``jax.grad``) gradients in straightforward ``jax.numpy`` under
``default_matmul_precision("highest")``. No flax, no kernels, no routing
code, no chunks: the delta rule is the SEQUENTIAL loop over tokens
(``lax.scan``) of step 6, attention a plain ``[T, T]`` masked softmax, the
expert sum a loop over the held experts with a mask (every held expert
computed for every token and the chosen ones kept).

``params`` is the unboxed tree ``Qwen3NextForCausalLM.init`` gives (the
same names), so a test hands both sides the same leaves. Steps 1-14 are
those of ``qwen3_next.py``'s docstring; the per-key-head column groups of
``W_qkvz`` and ``W_ba`` are taken by an explicit index here
(:func:`split_qkvz`, :func:`split_ba`), not by the module's reshapes.

DEPARTURES from the published model
(``Qwen/Qwen3-Next-80B-A3B-Instruct``), and what its public ``config.json``
does not fix — there was no network to read the released code, so each is
a possible departure:

* the row is the catalog's transcription of the public ``config.json``;
* step 4: the L2 norm of ``q`` and ``k`` is ``x * rsqrt(sum x^2 + 1e-6)``
  (the eps is not in the row);
* step 12: the routing weights stay float32 through the weighted sum (the
  released code rounds them to the hidden dtype first);
* multi-token prediction (one module after the last layer) is not built;
  the loss is next-token cross-entropy;
* the program computes step 6 in chunks of ``chunk_size`` (64) with the
  chunk's triangular inverse by halving (``ops/gated_delta.py``);
  here it is the sequential loop, which is the recurrence's definition;
* the final norm is the plain RMS norm, its scale holding ``1 + w``: the
  same function as the zero-centred norm of the other norms, stored
  otherwise (a loader adds one);
* the expert layer computes the part of the routed sum that the experts
  HELD here give (``held_experts_first``, ``num_experts`` of
  ``num_experts_total``): with every expert held it is the whole layer;
  :func:`moe` takes the range, so a test adds the shares up;
* seeded weights: how they are scaled is the benchmark family's
  (``benchmarks/families/qwen3_next/weights.py``), not the model's.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from fedml_tpu.models.llm.qwen3_next import Qwen3NextConfig

L2_EPS = 1e-6


def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def _zero_centred(x, w, eps):
    return _rms(x, eps) * (1.0 + w)


def _dense(cfg: Qwen3NextConfig, x, p):
    y = x @ p["kernel"]
    if "lora_a" in p:
        y = y + (x @ p["lora_a"]) @ p["lora_b"] * (cfg.lora_alpha / cfg.lora_rank)
    return y


def split_qkvz(cfg: Qwen3NextConfig, qkvz):
    """Step 1's columns ``[T, hk x (2 dk + 2 r dv)]`` -> ``q, k [T, hk,
    dk]``, ``v, z [T, hv, dv]``: key head ``h`` owns the ``h``-th group of
    ``2 dk + 2 r dv`` columns, ``[q_h | k_h | v_{rh} .. v_{rh + r - 1} |
    z_{rh} .. z_{rh + r - 1}]``."""
    hk, dk, dv = (cfg.linear_num_key_heads, cfg.linear_key_head_dim,
                  cfg.linear_value_head_dim)
    r = cfg.linear_num_value_heads // hk
    width = 2 * dk + 2 * r * dv
    group = lambda h, start, n: qkvz[:, h * width + start:h * width + start + n]
    q = jnp.stack([group(h, 0, dk) for h in range(hk)], axis=1)
    k = jnp.stack([group(h, dk, dk) for h in range(hk)], axis=1)
    v = jnp.stack([group(h, 2 * dk + j * dv, dv)
                   for h in range(hk) for j in range(r)], axis=1)
    z = jnp.stack([group(h, 2 * dk + (r + j) * dv, dv)
                   for h in range(hk) for j in range(r)], axis=1)
    return q, k, v, z


def split_ba(cfg: Qwen3NextConfig, ba):
    """Step 2's columns ``[T, hk x 2 r]`` -> ``b, a [T, hv]``: key head
    ``h`` owns ``[b_{rh} .. b_{rh + r - 1} | a_{rh} .. a_{rh + r - 1}]``."""
    hk = cfg.linear_num_key_heads
    r = cfg.linear_num_value_heads // hk
    b = jnp.stack([ba[:, h * 2 * r + j] for h in range(hk) for j in range(r)],
                  axis=1)
    a = jnp.stack([ba[:, h * 2 * r + r + j] for h in range(hk)
                   for j in range(r)], axis=1)
    return b, a


def recurrence(q, k, v, g, beta):
    """Step 6, one token after another: q, k ``[T, H, dk]``, v ``[T, H,
    dv]``, g and beta ``[T, H]`` -> o ``[T, H, dv]``."""
    def step(s, now):
        q_t, k_t, v_t, g_t, b_t = now
        s = jnp.exp(g_t)[:, None, None] * s
        delta = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", s, k_t))
        s = s + k_t[:, :, None] * delta[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t)

    heads, dk = q.shape[1:]
    _, o = jax.lax.scan(step, jnp.zeros((heads, dk, v.shape[-1]), jnp.float32),
                        (q, k, v, g, beta))
    return o


def linear_attention(cfg: Qwen3NextConfig, u, p):
    """Steps 1-7 for one sequence, u ``[T, hidden]``."""
    t = u.shape[0]
    hk, hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv, taps = (cfg.linear_key_head_dim, cfg.linear_value_head_dim,
                    cfg.linear_conv_kernel_dim)
    q, k, v, z = split_qkvz(cfg, _dense(cfg, u, p["in_proj_qkvz"]))     # 1
    b, a = split_ba(cfg, _dense(cfg, u, p["in_proj_ba"]))               # 2
    mixed = jnp.concatenate([q.reshape(t, -1), k.reshape(t, -1),         # 3
                             v.reshape(t, -1)], axis=-1)
    past = jnp.concatenate([jnp.zeros((taps - 1, cfg.conv_dim)), mixed])
    mixed = jax.nn.silu(sum(p["conv_kernel"][j] * past[j:j + t]
                            for j in range(taps)))
    q, k, v = jnp.split(mixed, [cfg.key_dim, 2 * cfg.key_dim], axis=-1)
    l2 = lambda x: x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True)
                                     + L2_EPS)
    q = l2(q.reshape(t, hk, dk)) / math.sqrt(dk)                        # 4
    k = l2(k.reshape(t, hk, dk))
    q, k = (jnp.repeat(x, hv // hk, axis=1) for x in (q, k))
    beta = jax.nn.sigmoid(b)                                            # 5
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"])
    o = recurrence(q, k, v.reshape(t, hv, dv), g, beta)                 # 6
    y = _rms(o, cfg.rms_norm_eps) * p["gate_norm_scale"] * jax.nn.silu(z)  # 7
    return _dense(cfg, y.reshape(t, hv * dv), p["out_proj"])


def rotary(x, rotary_dim: int, theta: float):
    """Rotary embedding on the leading ``rotary_dim`` lanes of ``x [T, H,
    D]`` at positions ``0 .. T - 1``, lane ``j`` paired with ``j +
    rotary_dim / 2``; the other lanes as they are."""
    t, half = x.shape[0], rotary_dim // 2
    freqs = 1.0 / theta ** (jnp.arange(0, rotary_dim, 2, dtype=jnp.float32)
                            / rotary_dim)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs   # [T, half]
    cos, sin = jnp.cos(angles)[:, None], jnp.sin(angles)[:, None]
    x1, x2 = x[..., :half], x[..., half:rotary_dim]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rotary_dim:]], axis=-1)


def attention(cfg: Qwen3NextConfig, u, p):
    """Steps 8-11 for one sequence."""
    t = u.shape[0]
    h, hkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    eps = cfg.rms_norm_eps
    q_gate = _dense(cfg, u, p["q_proj"]).reshape(t, h, 2 * d)           # 8
    q, gate = q_gate[..., :d], q_gate[..., d:]
    k = _dense(cfg, u, p["k_proj"]).reshape(t, hkv, d)
    v = _dense(cfg, u, p["v_proj"]).reshape(t, hkv, d)
    q = _zero_centred(q, p["q_norm"]["weight"], eps)                    # 9
    k = _zero_centred(k, p["k_norm"]["weight"], eps)
    q = rotary(q, cfg.rotary_dim, cfg.rope_theta)                       # 10
    k = rotary(k, cfg.rotary_dim, cfg.rope_theta)
    k, v = (jnp.repeat(x, h // hkv, axis=1) for x in (k, v))
    s = jnp.einsum("thd,shd->hts", q, k) / math.sqrt(d)                 # 11
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    o = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)
    o = o.reshape(t, h * d) * jax.nn.sigmoid(gate.reshape(t, h * d))
    return _dense(cfg, o, p["o_proj"])


def route(cfg: Qwen3NextConfig, u, p):
    """Step 12: ``(chosen [T, k], weights [T, k])`` over all experts."""
    probs = jax.nn.softmax(u @ p["router_weight"], axis=-1)
    _, chosen = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    kept = jnp.take_along_axis(probs, chosen, axis=-1)
    return chosen, kept / jnp.sum(kept, -1, keepdims=True)


def _swiglu(x, p):
    return (jax.nn.silu(x @ p["gate_proj"]["kernel"])
            * (x @ p["up_proj"]["kernel"])) @ p["down_proj"]["kernel"]


def routed(cfg: Qwen3NextConfig, u, p, first=None, count=None):
    """Step 13 for the experts ``first .. first + count`` held
    (``p["experts"]`` holds those ``count`` and no others): ``(r [T,
    hidden], assignments of each held expert)``."""
    first = cfg.held_experts_first if first is None else first
    count = cfg.num_experts if count is None else count
    chosen, weights = route(cfg, u, p)
    ex = p["experts"]
    r = jnp.zeros_like(u)
    counts = []
    for e in range(count):
        out = (jax.nn.silu(u @ ex["gate_proj"][e]) * (u @ ex["up_proj"][e])) \
            @ ex["down_proj"][e]
        mine = chosen == first + e                                   # [T, k]
        r = r + jnp.sum(jnp.where(mine, weights, 0.0), -1, keepdims=True) * out
        counts.append(jnp.sum(mine))
    return r, jnp.stack(counts)


def shared(cfg: Qwen3NextConfig, u, p):
    """Step 14's second term: the shared expert under its gate."""
    return jax.nn.sigmoid(u @ p["shared_gate"]["kernel"]) * _swiglu(
        u, p["shared"])


def moe(cfg: Qwen3NextConfig, u, p, first=None, count=None):
    """Steps 12-14: the held experts' part plus the gated shared expert."""
    r, counts = routed(cfg, u, p, first, count)
    return r + shared(cfg, u, p), counts


def forward(cfg: Qwen3NextConfig, params, tokens):
    """``(logits [B, T, V], assignments per layer and held expert [layers,
    held])``."""
    p = _f32(params["params"] if "params" in params else params)
    eps = cfg.rms_norm_eps

    def one(row):
        x = p["embed_tokens"][row]
        counts = []
        for i in range(cfg.num_hidden_layers):
            layer = p[f"layer_{i}"]
            u = _zero_centred(x, layer["input_norm"]["weight"], eps)
            if cfg.is_linear(i):
                x = x + linear_attention(cfg, u, layer["linear_attn"])
            else:
                x = x + attention(cfg, u, layer["attn"])
            y, n = moe(cfg, _zero_centred(
                x, layer["post_attn_norm"]["weight"], eps), layer["moe"])
            x = x + y
            counts.append(n)
        x = _rms(x, eps) * p["final_norm"]["scale"]
        return x @ p["lm_head"], jnp.stack(counts)

    with jax.default_matmul_precision("highest"):
        logits, counts = jax.vmap(one)(tokens)
    return logits, counts.sum(0)


def loss(cfg: Qwen3NextConfig, params, tokens, targets):
    """Mean next-token cross-entropy over all positions."""
    logits, _ = forward(cfg, params, tokens)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))
