"""The plain float32 statement of ``longcat_flash.py``: forward, loss and
(through ``jax.grad``) gradients in straightforward ``jax.numpy`` under
``default_matmul_precision("highest")``. No flax, no kernels, no routing
code: heads in the PUBLISHED lane order (``[nope | rope]``, rotary pairs of
NEIGHBOURING lanes), the key built by an explicit broadcast of the one
rotary key, a plain ``[T, T]`` masked softmax, the expert sum a loop over
the held experts with a mask (every held expert computed for every token
and the chosen ones kept), the identity experts an explicit sum.

``params`` is the unboxed tree ``LongcatFlashForCausalLM.init`` gives with
``q_b_proj``'s and ``kv_a_proj``'s columns (kernel and ``lora_b``) in the
published order: :func:`published` makes it from the module's tree, and is
where the differences of layout between the two sides are written down.

Steps 1-8 are those of ``longcat_flash.py``'s docstring. DEPARTURES from
the published model (``meituan-longcat/LongCat-Flash-Chat``), and what its
public ``config.json`` does not fix — there was no network to read the
released code, so each is a possible departure:

* the double layer: two pre-norm attention sublayers and two pre-norm dense
  SwiGLU sublayers, the expert branch reading the first FFN's normed input
  and added to the stream after the second FFN; no norm after a branch;
* ``mla_scale_q_lora`` multiplies all of ``q`` (nope and rope lanes) by
  ``sqrt(hidden / q_lora_rank)`` after ``W_qb``; ``mla_scale_kv_lora``
  multiplies the normed kv latent (not the rotary key) by ``sqrt(hidden /
  kv_lora_rank)`` before ``W_kvb``;
* rotary pairs are neighbouring lanes of the rope part (the module turns
  halves: a permutation of ``W_qb``'s and ``W_kva``'s rope columns,
  :func:`published`); theta 1e7, no scaling; the softmax's scale is
  ``(nope + rope) ** -0.5``;
* routing: a softmax over the FFN experts and the identity experts
  together; the router has no bias of its own; the selection bias (the
  PID-controlled ``e_score_correction_bias``, frozen here) picks and does
  not weigh; the weights are ``routed_scaling_factor`` times the chosen
  probabilities, NOT re-normalised over the chosen;
* an identity expert returns its input: it adds ``w_e u``;
* the head is untied (the config gives no ``tie_word_embeddings``);
  multi-token prediction is not built (the config declares none);
* the expert branch computes the part of the routed sum that the FFN
  experts HELD here give (``held_experts_first``, ``n_routed_experts`` of
  ``n_routed_experts_total``) and the whole identity sum: :func:`moe`
  takes the range, so a test adds the shares up.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu.models.llm.layers import published_lanes
from fedml_tpu.models.llm.longcat_flash import LongcatFlashConfig


def _interleaved(rope: int) -> np.ndarray:
    """Rope lanes as published (neighbours paired) from the lanes here
    (halves paired): published lane ``2j`` is lane ``j`` here, ``2j + 1`` is
    ``j + rope / 2``."""
    return np.stack([np.arange(rope // 2),
                     np.arange(rope // 2) + rope // 2], -1).reshape(-1)


def published(cfg: LongcatFlashConfig, params):
    """The module's tree with every ``q_b_proj``'s and ``kv_a_proj``'s
    columns (kernel and ``lora_b``) moved to the published lane order."""
    rope, d = cfg.qk_rope_head_dim, cfg.head_dim
    # a head [rope | nope] -> [nope | rope], then the rope lanes' pairs
    here = published_lanes(cfg).reshape(cfg.num_attention_heads, d)
    q_lanes = np.concatenate(
        [here[:, :d - rope], here[:, d - rope:][:, _interleaved(rope)]],
        axis=1).reshape(-1)
    lat = cfg.kv_lora_rank
    kv_lanes = np.concatenate([np.arange(lat), lat + _interleaved(rope)])

    def move(path, leaf):
        names = [str(getattr(p, "key", p)) for p in path]
        if names[-1] in ("kernel", "lora_b"):
            if "q_b_proj" in names:
                return leaf[:, q_lanes]
            if "kv_a_proj" in names:
                return leaf[:, kv_lanes]
        return leaf

    return jax.tree_util.tree_map_with_path(move, params)


def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _dense(cfg: LongcatFlashConfig, x, p):
    y = x @ p["kernel"]
    if "lora_a" in p:
        y = y + (x @ p["lora_a"]) @ p["lora_b"] * (cfg.lora_alpha / cfg.lora_rank)
    return y


def rotary(cfg: LongcatFlashConfig, x):
    """``x`` ``[T, ..., rope]``: every lane pair ``(2j, 2j + 1)`` of token
    ``t`` turned by ``t / theta ** (2 j / rope)``."""
    t, r = x.shape[0], cfg.qk_rope_head_dim // 2
    freqs = cfg.rope_theta ** (-jnp.arange(r, dtype=jnp.float32) / r)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    shape = (t,) + (1,) * (x.ndim - 2) + (r,)
    cos, sin = jnp.cos(angles).reshape(shape), jnp.sin(angles).reshape(shape)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def attention(cfg: LongcatFlashConfig, u, p):
    """Steps 3-5 for one sequence, u ``[T, hidden]``."""
    t = u.shape[0]
    h, nope, rope, dv = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                         cfg.qk_rope_head_dim, cfg.v_head_dim)
    eps, lat = cfg.rms_norm_eps, cfg.kv_lora_rank
    q_scale = math.sqrt(cfg.hidden_size / cfg.q_lora_rank)
    kv_scale = math.sqrt(cfg.hidden_size / cfg.kv_lora_rank)
    c_q = _rms_norm(_dense(cfg, u, p["q_a_proj"]), p["q_a_norm"]["scale"], eps)
    q = q_scale * _dense(cfg, c_q, p["q_b_proj"]).reshape(t, h, nope + rope)
    q_nope, q_r = q[..., :nope], q[..., nope:]                          # 3
    kv_a = _dense(cfg, u, p["kv_a_proj"])                               # 4
    c_kv, k_r = kv_a[:, :lat], kv_a[:, lat:]
    c_kv = kv_scale * _rms_norm(c_kv, p["kv_a_norm"]["scale"], eps)
    kv = _dense(cfg, c_kv, p["kv_b_proj"]).reshape(t, h, nope + dv)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_r, k_r = rotary(cfg, q_r), rotary(cfg, k_r)                       # 5
    q = jnp.concatenate([q_nope, q_r], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_r[:, None, :], (t, h, rope))], axis=-1)
    s = jnp.einsum("thd,shd->hts", q, k) / math.sqrt(nope + rope)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    o = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)
    return _dense(cfg, o.reshape(t, h * dv), p["o_proj"])


def swiglu(u, p):
    """A dense FFN: ``p`` holds ``gate_proj``, ``up_proj``, ``down_proj``."""
    return (jax.nn.silu(u @ p["gate_proj"]["kernel"])
            * (u @ p["up_proj"]["kernel"])) @ p["down_proj"]["kernel"]


def route(cfg: LongcatFlashConfig, u, p):
    """Step 6: ``(chosen [T, k], weights [T, k])`` over the FFN experts
    and the identity experts."""
    probs = jax.nn.softmax(u @ p["router_weight"], axis=-1)
    _, chosen = jax.lax.top_k(probs + p["router_bias"], cfg.moe_topk)
    return chosen, cfg.routed_scaling_factor * jnp.take_along_axis(
        probs, chosen, axis=-1)


def moe(cfg: LongcatFlashConfig, u, p, first=None, count=None):
    """Steps 6-8 for the FFN experts ``first .. first + count`` held
    (``p["experts"]`` holds those ``count`` and no others): ``(out [T,
    hidden], assignments of each held expert, assignments to an identity
    expert)``."""
    first = cfg.held_experts_first if first is None else first
    count = cfg.n_routed_experts if count is None else count
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
    zero, picked = identity(cfg, u, chosen, weights)                 # step 8
    return r + zero, jnp.stack(counts), picked


def identity(cfg: LongcatFlashConfig, u, chosen, weights):
    """Step 8: ``(what the identity experts add [T, hidden], how many were
    chosen)`` — the same on every chip, whatever it holds."""
    mine = chosen >= cfg.experts_total
    return (jnp.sum(jnp.where(mine, weights, 0.0), -1, keepdims=True) * u,
            jnp.sum(mine))


def double_layer(cfg: LongcatFlashConfig, x, layer):
    """Steps 1-2 for one sequence: ``(x, held counts, identity count)``."""
    eps = cfg.rms_norm_eps
    for i in (0, 1):
        u = _rms_norm(x, layer[f"input_norm_{i}"]["scale"], eps)
        x = x + attention(cfg, u, layer[f"attn_{i}"])
        u = _rms_norm(x, layer[f"post_attn_norm_{i}"]["scale"], eps)
        if i == 0:
            m, counts, zero = moe(cfg, u, layer["moe"])
        x = x + swiglu(u, layer[f"mlp_{i}"])
    return x + m, counts, zero


def forward(cfg: LongcatFlashConfig, params, tokens):
    """``(logits [B, T, V], assignments per double layer and held expert
    [layers, held], identity assignments per double layer [layers])``;
    ``params`` in the published lane order."""
    p = _f32(params["params"] if "params" in params else params)

    def one(row):
        x = p["embed_tokens"][row]
        counts, zeros = [], []
        for i in range(cfg.num_layers):
            x, n, z = double_layer(cfg, x, p[f"layer_{i}"])
            counts.append(n)
            zeros.append(z)
        x = _rms_norm(x, p["final_norm"]["scale"], cfg.rms_norm_eps)
        return x @ p["lm_head"], jnp.stack(counts), jnp.stack(zeros)

    with jax.default_matmul_precision("highest"):
        logits, counts, zeros = jax.vmap(one)(tokens)
    return logits, counts.sum(0), zeros.sum(0)


def loss(cfg: LongcatFlashConfig, params, tokens, targets):
    """Mean next-token cross-entropy over all positions."""
    logits, _, _ = forward(cfg, params, tokens)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))
