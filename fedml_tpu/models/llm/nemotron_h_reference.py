"""The plain float32 statement of ``nemotron_h.py``: forward, loss and
(through ``jax.grad``) gradients in straightforward ``jax.numpy`` under
``default_matmul_precision("highest")``. No flax, no kernels, no routing
code, no chunks: the recurrence is the SEQUENTIAL loop over tokens
(``lax.scan``), the expert sum a loop over the held experts with a mask,
every held expert computed for every token and the chosen ones kept.

``params`` is the unboxed tree ``NemotronHForCausalLM.init`` gives (the
same names), so a test hands both sides the same leaves.

Steps 1-9 are those of ``nemotron_h.py``'s docstring. DEPARTURES from the
published model (``nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16``), and
what its public ``config.json`` does not fix — there was no network to
read the released code, so each is a possible departure:

* multi-token prediction (``num_nextn_predict_layers`` 1,
  ``mtp_hybrid_override_pattern`` ``*E``) is not built: a module after the
  last layer that shares the head; the loss here is next-token
  cross-entropy;
* the attention layers apply no rotary embedding (``rope_theta`` and
  ``partial_rotary_factor`` of the row are read by nothing);
* step 5: the gate is applied before the group norm (``norm(y * silu(z))``,
  not ``norm(y) * silu(z)``), the norm is over each of the ``n_groups``
  groups of channels;
* step 3: ``dt`` is not clamped above (``time_step_limit`` is not in the
  row); ``time_step_min / max / floor`` only say how a trained ``dt_bias``
  was drawn;
* step 7: the selection bias is added for the choice only, the weights are
  the chosen scores re-normalised (``norm_topk_prob``) and scaled by
  ``routed_scaling_factor``; ``n_group = topk_group = 1`` is no limit;
* the expert layer computes the part of the routed sum that the experts
  HELD here give (``held_experts_first``, ``n_routed_experts`` of
  ``n_routed_experts_total``): with every expert held it is the whole
  layer; :func:`moe` takes the range, so a test adds the shares up.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from fedml_tpu.models.llm.nemotron_h import NemotronHConfig


def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _dense(cfg: NemotronHConfig, x, p):
    y = x @ p["kernel"]
    if "lora_a" in p:
        y = y + (x @ p["lora_a"]) @ p["lora_b"] * (cfg.lora_alpha / cfg.lora_rank)
    return y


def _relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def recurrence(x, dt, a, b, c):
    """Step 4 without the skip, one token after another: x ``[T, H, P]``,
    dt ``[T, H]``, a ``[H]``, b and c ``[T, G, N]`` -> y ``[T, H, P]``."""
    t, heads, p = x.shape
    per = heads // b.shape[1]

    def step(h, now):
        x_t, dt_t, b_t, c_t = now
        b_t, c_t = jnp.repeat(b_t, per, axis=0), jnp.repeat(c_t, per, axis=0)
        h = (jnp.exp(dt_t * a)[:, None, None] * h
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return h, jnp.einsum("hpn,hn->hp", h, c_t)

    _, y = jax.lax.scan(
        step, jnp.zeros((heads, p, b.shape[2]), jnp.float32), (x, dt, b, c))
    return y


def mamba(cfg: NemotronHConfig, u, p):
    """Steps 1-6 for one sequence, u ``[T, hidden]``."""
    t = u.shape[0]
    h, hp, g, n = (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.n_groups,
                   cfg.ssm_state_size)
    d, taps = cfg.mamba_inner, cfg.conv_kernel
    zxbcdt = _dense(cfg, u, p["in_proj"])                              # 1
    z, xbc, dt = jnp.split(zxbcdt, [d, d + cfg.conv_dim], axis=-1)
    past = jnp.concatenate([jnp.zeros((taps - 1, cfg.conv_dim)), xbc])  # 2
    xbc = jax.nn.silu(p["conv_bias"] + sum(
        p["conv_kernel"][j] * past[j:j + t] for j in range(taps)))
    x, b, c = jnp.split(xbc, [d, d + g * n], axis=-1)                   # 3
    x = x.reshape(t, h, hp)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = recurrence(x, dt, -jnp.exp(p["A_log"]), b.reshape(t, g, n),     # 4
                   c.reshape(t, g, n)) + p["D"][:, None] * x
    gated = (y.reshape(t, d) * jax.nn.silu(z)).reshape(t, g, d // g)    # 5
    normed = gated * jax.lax.rsqrt(
        jnp.mean(gated * gated, -1, keepdims=True) + cfg.rms_norm_eps)
    return _dense(cfg, normed.reshape(t, d) * p["gate_norm_scale"],     # 6
                  p["out_proj"])


def attention(cfg: NemotronHConfig, u, p):
    t = u.shape[0]
    hq, hk, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    q = _dense(cfg, u, p["q_proj"]).reshape(t, hq, d)
    k = _dense(cfg, u, p["k_proj"]).reshape(t, hk, d)
    v = _dense(cfg, u, p["v_proj"]).reshape(t, hk, d)
    k, v = (jnp.repeat(z, hq // hk, axis=1) for z in (k, v))
    s = jnp.einsum("thd,shd->hts", q, k) / math.sqrt(d)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    o = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)
    return _dense(cfg, o.reshape(t, hq * d), p["o_proj"])


def route(cfg: NemotronHConfig, u, p):
    """Step 7: ``(chosen [T, k], weights [T, k])`` over all experts."""
    s = jax.nn.sigmoid(u @ p["router_weight"])
    _, chosen = jax.lax.top_k(s + p["router_bias"], cfg.num_experts_per_tok)
    kept = jnp.take_along_axis(s, chosen, axis=-1)
    return chosen, kept / (jnp.sum(kept, -1, keepdims=True) + 1e-20) \
        * cfg.routed_scaling_factor


def routed(cfg: NemotronHConfig, u, p, first=None, count=None):
    """Step 8 in the latent, for the experts ``first .. first + count``
    held (``p["experts"]`` holds those ``count`` and no others): ``(r [T,
    latent], assignments of each held expert)``."""
    first = cfg.held_experts_first if first is None else first
    count = cfg.n_routed_experts if count is None else count
    chosen, weights = route(cfg, u, p)
    latent = u @ p["latent_in"]["kernel"]
    r = jnp.zeros_like(latent)
    counts = []
    for e in range(count):
        out = _relu2(latent @ p["experts"]["up_proj"][e]) \
            @ p["experts"]["down_proj"][e]
        mine = chosen == first + e                                   # [T, k]
        r = r + jnp.sum(jnp.where(mine, weights, 0.0), -1, keepdims=True) * out
        counts.append(jnp.sum(mine))
    return r, jnp.stack(counts)


def shared(cfg: NemotronHConfig, u, p):
    return _relu2(u @ p["shared"]["up_proj"]["kernel"]) \
        @ p["shared"]["down_proj"]["kernel"]


def moe(cfg: NemotronHConfig, u, p, first=None, count=None):
    """Steps 7-9: the held experts' part, back in the hidden width, plus
    the shared expert."""
    r, counts = routed(cfg, u, p, first, count)
    return r @ p["latent_out"]["kernel"] + shared(cfg, u, p), counts


def forward(cfg: NemotronHConfig, params, tokens):
    """``(logits [B, T, V], assignments per expert layer and held expert
    [layers, held])``."""
    p = _f32(params["params"] if "params" in params else params)

    def one(row):
        x = p["embed_tokens"][row]
        counts = []
        for i in range(cfg.num_hidden_layers):
            layer, kind = p[f"layer_{i}"], cfg.layer_kind(i)
            u = _rms_norm(x, layer["input_norm"]["scale"], cfg.rms_norm_eps)
            if kind == "M":
                x = x + mamba(cfg, u, layer["mamba"])
            elif kind == "*":
                x = x + attention(cfg, u, layer["attn"])
            else:
                y, n = moe(cfg, u, layer["moe"])
                x = x + y
                counts.append(n)
        x = _rms_norm(x, p["final_norm"]["scale"], cfg.rms_norm_eps)
        return x @ p["lm_head"], jnp.stack(counts)

    with jax.default_matmul_precision("highest"):
        logits, counts = jax.vmap(one)(tokens)
    return logits, counts.sum(0)


def loss(cfg: NemotronHConfig, params, tokens, targets):
    """Mean next-token cross-entropy over all positions."""
    logits, _ = forward(cfg, params, tokens)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))
