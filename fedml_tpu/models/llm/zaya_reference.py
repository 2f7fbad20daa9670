"""The plain float32 statement of ``zaya.py``: forward, loss and (through
``jax.grad``) gradients in straightforward ``jax.numpy``. No flax, no
kernels, no routing code: the expert layer is a mask over experts, every
expert computed for every token and the chosen one kept.

``params`` is the unboxed tree ``ZayaForCausalLM.init`` gives (the same
names), so a test hands both sides the same leaves.

Steps 1-9 are those of ``zaya.py``'s docstring. POSSIBLE DEPARTURES from
the released model — the public ``config.json`` does not fix them, there
was no network to read the reference code, and they are written from the
CCA paper (arXiv:2510.04476) and the ZAYA1 report (arXiv:2511.17127):

* step 2: the second convolution's grouping (block-diagonal by head) and
  both convolutions' biases;
* step 3: the q-k mean and its group mean added to the mixed q and k;
* step 4: the value of the second half of the key-value heads taken from
  the previous token's ``h`` through its own projection;
* step 5: l2 normalisation of q and k to ``sqrt(D)`` (an epsilon of 1e-6
  under the root), one learned temperature per key-value head on k;
* step 8: the router MLP's depth (three matrices after the down
  projection and an RMSNorm with a learned scale), gelu (tanh form), the
  carried state's form ``s_l = r + gamma_l * s_{l-1}``; no balancing bias
  and no skip expert (the row says 16 experts, 1 a token, and names
  neither).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from fedml_tpu.models.llm.zaya import L2_EPS, ZayaConfig


def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _dense(cfg: ZayaConfig, x, p):
    y = x @ p["kernel"]
    if "lora_a" in p:
        y = y + (x @ p["lora_a"]) @ p["lora_b"] * (cfg.lora_alpha / cfg.lora_rank)
    return y


def _shift(x, by=1):
    """``y[t] = x[t - by]``, zeros before ``t = 0``; x ``[T, ...]``."""
    if by == 0:
        return x
    return jnp.concatenate([jnp.zeros_like(x[:by]), x[:-by]], axis=0)


def _rope(x, theta, rot):
    """Halves convention on the first ``rot`` dimensions; x ``[T, H, D]``."""
    t = x.shape[0]
    freqs = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[:, None], jnp.sin(angles)[:, None]
    x1, x2, rest = x[..., :rot // 2], x[..., rot // 2:rot], x[..., rot:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def attention(cfg: ZayaConfig, h, p, value_shift: bool = True):
    """Steps 1-7 for one sequence, h ``[T, hidden]``."""
    t = h.shape[0]
    hq, hk, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    g = hq // hk
    q_lat = _dense(cfg, h, p["q_proj"])                       # 1
    k_lat = _dense(cfg, h, p["k_proj"])
    c = jnp.concatenate([q_lat, k_lat], -1)                   # 2
    c1 = p["conv0_bias"] + sum(
        p["conv0_kernel"][j] * _shift(c, j) for j in range(cfg.cca_time0))
    c1 = c1.reshape(t, hq + hk, d)
    c2 = p["conv1_bias"].reshape(hq + hk, d) + sum(
        jnp.einsum("thd,hde->the", _shift(c1, j), p["conv1_kernel"][j])
        for j in range(cfg.cca_time1))
    qc, kc = c2[:, :hq], c2[:, hq:]
    q_lat, k_lat = q_lat.reshape(t, hq, d), k_lat.reshape(t, hk, d)
    mq = (q_lat + jnp.repeat(k_lat, g, axis=1)) / 2           # 3
    mk = mq.reshape(t, hk, g, d).mean(2)
    q, k = qc + mq, kc + mk
    prev = _shift(h) if value_shift else jnp.zeros_like(h)    # 4
    v = jnp.concatenate(
        [_dense(cfg, h, p["v_proj"]).reshape(t, hk // 2, d),
         _dense(cfg, prev, p["v_prev_proj"]).reshape(t, hk // 2, d)], axis=1)
    norm = lambda x: math.sqrt(d) * x / jnp.sqrt(                # 5
        jnp.sum(x * x, -1, keepdims=True) + L2_EPS)
    q, k = norm(q), norm(k) * p["k_temp"][:, None]
    q = _rope(q, cfg.rope_theta, cfg.rotary_dim)              # 6
    k = _rope(k, cfg.rope_theta, cfg.rotary_dim)
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)  # 7
    s = jnp.einsum("thd,shd->hts", q, k) / math.sqrt(d)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    o = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)
    return _dense(cfg, o.reshape(t, hq * d), p["o_proj"])


def router(cfg: ZayaConfig, h, state, p):
    """Step 8: ``(p [T, E], s_l)`` from ``h`` and ``s_{l-1}``."""
    state = h @ p["down"] + p["gamma"] * state
    n = _rms_norm(state, p["norm_scale"], cfg.rms_norm_eps)
    z = jax.nn.gelu(jax.nn.gelu(n @ p["w1"]) @ p["w2"]) @ p["w3"]
    return jax.nn.softmax(z, axis=-1), state


def experts(cfg: ZayaConfig, h, probs, p):
    """Step 9 as a mask over experts; also each expert's token count."""
    chosen = jnp.argmax(probs, axis=-1)
    y = jnp.zeros_like(h)
    for e in range(cfg.num_experts):
        out = (jax.nn.silu(h @ p["gate_proj"][e]) * (h @ p["up_proj"][e])
               ) @ p["down_proj"][e]
        y = y + jnp.where((chosen == e)[:, None], probs[:, e:e + 1] * out, 0)
    counts = jnp.sum(chosen[:, None] == jnp.arange(cfg.num_experts), axis=0)
    return y, counts


def forward(cfg: ZayaConfig, params, tokens, value_shift: bool = True,
            carry_state: bool = True):
    """``(logits [B, T, V], tokens per layer and expert [L, E])``. The two
    switches take a mechanism out, for the tests that show it matters."""
    p = _f32(params["params"] if "params" in params else params)

    def one(row):
        x = p["embed_tokens"][row]
        state = jnp.zeros((row.shape[0], cfg.router_hidden_size), jnp.float32)
        counts = []
        for i in range(cfg.num_hidden_layers):
            layer = p[f"layer_{i}"]
            h = _rms_norm(x, layer["input_norm"]["scale"], cfg.rms_norm_eps)
            x = x + attention(cfg, h, layer["attn"], value_shift)
            h = _rms_norm(x, layer["post_attn_norm"]["scale"],
                          cfg.rms_norm_eps)
            probs, new_state = router(cfg, h, state,
                                      layer["moe"]["router_mlp"])
            state = new_state if carry_state else jnp.zeros_like(state)
            y, n = experts(cfg, h, probs, layer["moe"]["experts"])
            x = x + y
            counts.append(n)
        x = _rms_norm(x, p["final_norm"]["scale"], cfg.rms_norm_eps)
        return x @ p["embed_tokens"].T, jnp.stack(counts)

    with jax.default_matmul_precision("highest"):
        logits, counts = jax.vmap(one)(tokens)
    return logits, counts.sum(0)


def loss(cfg: ZayaConfig, params, tokens, targets):
    """Mean next-token cross-entropy over all positions."""
    logits, _ = forward(cfg, params, tokens)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))
