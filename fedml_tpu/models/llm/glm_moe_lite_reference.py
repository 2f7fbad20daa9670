"""The plain float32 statement of ``glm_moe_lite.py``: forward, loss and
(through ``jax.grad``) gradients in straightforward ``jax.numpy`` under
``default_matmul_precision("highest")``. No flax, no kernels, no routing
code: heads in the PUBLISHED lane order (``[nope | rope]``), the key built
by an explicit broadcast of the one rotary key, a plain ``[T, T]`` masked
softmax, the expert sum a loop over the experts with a mask, every expert
computed for every token and the chosen ones kept.

``params`` is the unboxed tree ``GlmMoeLiteForCausalLM.init`` gives with
``q_b_proj``'s columns (kernel and ``lora_b``) in the published order:
:func:`published` makes it from the module's tree, and is where the one
difference of layout between the two sides is written down.

Steps 1-8 are those of ``glm_moe_lite.py``'s docstring. DEPARTURES from the
published model (``zai-org/GLM-4.7-Flash``), and what its public
``config.json`` does not fix — there was no network to read the released
code, so each is a possible departure:

* multi-token prediction (``num_nextn_predict_layers`` 1) is not built: a
  module after the last layer that shares the head; the loss here is
  next-token cross-entropy (the public Transformers code for this
  ``model_type`` is understood to drop those weights at load: unverified);
* rotary pairs are the two halves of the ``rope`` lanes (lane ``j`` with
  lane ``j + rope / 2``, as ``layers.apply_rope``); released code may pair
  neighbours instead, which is a permutation of ``W_qb``'s and ``W_kva``'s
  rotary columns that a loader applies and seeded weights cannot see;
* the module lays a head's score lanes out ``[rope | nope]``; this file
  keeps ``[nope | rope]`` (:func:`published`);
* both latent norms use ``rms_norm_eps``; a layer has two pre-norms and no
  norm after a branch; ``rope_scaling`` is null, so the softmax's scale is
  ``(nope + rope) ** -0.5`` with no correction;
* step 6: the selection bias is added for the choice only, the weights are
  the chosen scores re-normalised (``norm_topk_prob``, ``+ 1e-20``) and
  scaled by ``routed_scaling_factor``; ``n_group = topk_group = 1`` is no
  limit;
* step 8: the shared expert's output is added unweighted.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from fedml_tpu.models.llm.glm_moe_lite import GlmMoeLiteConfig
from fedml_tpu.models.llm.layers import published_lanes


def published(cfg: GlmMoeLiteConfig, params):
    """The module's tree with every ``q_b_proj``'s columns (kernel and
    ``lora_b``) moved to the published lane order."""
    lanes = published_lanes(cfg)

    def move(path, leaf):
        names = [str(getattr(p, "key", p)) for p in path]
        if "q_b_proj" in names and names[-1] in ("kernel", "lora_b"):
            return leaf[:, lanes]
        return leaf

    return jax.tree_util.tree_map_with_path(move, params)


def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _dense(cfg: GlmMoeLiteConfig, x, p):
    y = x @ p["kernel"]
    if "lora_a" in p:
        y = y + (x @ p["lora_a"]) @ p["lora_b"] * (cfg.lora_alpha / cfg.lora_rank)
    return y


def rotary(cfg: GlmMoeLiteConfig, x):
    """``x`` ``[T, ..., rope]``: every lane pair ``(j, j + rope / 2)`` of
    token ``t`` turned by ``t / theta ** (2 j / rope)``."""
    t, r = x.shape[0], cfg.qk_rope_head_dim // 2
    freqs = cfg.rope_theta ** (-jnp.arange(r, dtype=jnp.float32) / r)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    shape = (t,) + (1,) * (x.ndim - 2) + (r,)
    cos, sin = jnp.cos(angles).reshape(shape), jnp.sin(angles).reshape(shape)
    x1, x2 = x[..., :r], x[..., r:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(cfg: GlmMoeLiteConfig, u, p):
    """Steps 1-4 for one sequence, u ``[T, hidden]``."""
    t = u.shape[0]
    h, nope, rope, dv = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                         cfg.qk_rope_head_dim, cfg.v_head_dim)
    eps = cfg.rms_norm_eps
    c_q = _rms_norm(_dense(cfg, u, p["q_a_proj"]), p["q_a_norm"]["scale"], eps)
    q = _dense(cfg, c_q, p["q_b_proj"]).reshape(t, h, nope + rope)      # 1
    q_nope, q_r = q[..., :nope], q[..., nope:]
    kv_a = _dense(cfg, u, p["kv_a_proj"])                               # 2
    c_kv, k_r = kv_a[:, :cfg.kv_lora_rank], kv_a[:, cfg.kv_lora_rank:]
    c_kv = _rms_norm(c_kv, p["kv_a_norm"]["scale"], eps)
    kv = _dense(cfg, c_kv, p["kv_b_proj"]).reshape(t, h, nope + dv)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_r, k_r = rotary(cfg, q_r), rotary(cfg, k_r)                       # 3
    every_head = jnp.broadcast_to(k_r[:, None, :], (t, h, rope))
    q = jnp.concatenate([q_nope, q_r], axis=-1)
    k = jnp.concatenate([k_nope, every_head], axis=-1)
    s = jnp.einsum("thd,shd->hts", q, k) / math.sqrt(nope + rope)       # 4
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    o = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)
    return _dense(cfg, o.reshape(t, h * dv), p["o_proj"])


def swiglu(u, p):
    """Steps 5 and 8: ``p`` holds ``gate_proj``, ``up_proj``, ``down_proj``."""
    return (jax.nn.silu(u @ p["gate_proj"]["kernel"])
            * (u @ p["up_proj"]["kernel"])) @ p["down_proj"]["kernel"]


def route(cfg: GlmMoeLiteConfig, u, p):
    """Step 6: ``(chosen [T, k], weights [T, k])``."""
    s = jax.nn.sigmoid(u @ p["router_weight"])
    _, chosen = jax.lax.top_k(s + p["router_bias"], cfg.num_experts_per_tok)
    kept = jnp.take_along_axis(s, chosen, axis=-1)
    return chosen, kept / (jnp.sum(kept, -1, keepdims=True) + 1e-20) \
        * cfg.routed_scaling_factor


def moe(cfg: GlmMoeLiteConfig, u, p):
    """Steps 6-8: ``(out [T, hidden], assignments of each expert)``."""
    chosen, weights = route(cfg, u, p)
    r, counts = jnp.zeros_like(u), []
    for e in range(cfg.n_routed_experts):
        ex = p["experts"]
        out = (jax.nn.silu(u @ ex["gate_proj"][e])
               * (u @ ex["up_proj"][e])) @ ex["down_proj"][e]
        mine = chosen == e                                           # [T, k]
        r = r + jnp.sum(jnp.where(mine, weights, 0.0), -1, keepdims=True) * out
        counts.append(jnp.sum(mine))
    return r + swiglu(u, p["shared"]), jnp.stack(counts)


def forward(cfg: GlmMoeLiteConfig, params, tokens):
    """``(logits [B, T, V], assignments per expert layer and expert
    [layers, experts])``; ``params`` in the published lane order."""
    p = _f32(params["params"] if "params" in params else params)

    def one(row):
        x = p["embed_tokens"][row]
        counts = []
        for i in range(cfg.num_hidden_layers):
            layer = p[f"layer_{i}"]
            u = _rms_norm(x, layer["input_norm"]["scale"], cfg.rms_norm_eps)
            x = x + attention(cfg, u, layer["attn"])
            u = _rms_norm(x, layer["post_attn_norm"]["scale"],
                          cfg.rms_norm_eps)
            if cfg.is_dense(i):
                x = x + swiglu(u, layer["mlp"])
            else:
                y, n = moe(cfg, u, layer["moe"])
                x = x + y
                counts.append(n)
        x = _rms_norm(x, p["final_norm"]["scale"], cfg.rms_norm_eps)
        return x @ p["lm_head"], jnp.stack(counts)

    with jax.default_matmul_precision("highest"):
        logits, counts = jax.vmap(one)(tokens)
    return logits, counts.sum(0)


def loss(cfg: GlmMoeLiteConfig, params, tokens, targets):
    """Mean next-token cross-entropy over all positions."""
    logits, _ = forward(cfg, params, tokens)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))
