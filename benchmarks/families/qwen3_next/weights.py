"""The leaves of ``Qwen3NextForCausalLM`` by their flax names, from the
configuration alone: ``{name: (shape, dtype, std)}``; std None = ones, std
0 = zeros. Layer ``i`` is full attention where ``(i + 1) %
full_attention_interval == 0``, else Gated DeltaNet; every layer has an
expert layer.

As the other families': matrices a token is multiplied by are bfloat16
with a fan-in std, adapters and norm scales float32, ``lora_b`` NOT zero.
What is scaled for this family, and why (the configuration's ``assumed``
says the same):

* the embedding has std 1 and what a branch adds to the residual stream is
  scaled by ``(8 x layers) ** -0.5`` (``out_proj``, ``o_proj``, the experts'
  and the shared expert's ``down_proj``: two branches a layer, as
  ``glm4_moe_lite``'s): a seeded router spreads its tokens only while what
  every token shares stays small beside what tells tokens apart; the
  untied head keeps ``llama``'s std 0.02;
* the router's logits have std ``ROUTER_STD`` (1), so the softmax over 512
  experts tells a token's ten apart from the rest;
* the zero-centred norms' ``w`` are zero (a gain of one), except the
  attention's ``q_norm`` and ``k_norm``: normals of std ``QK_NORM_STD`` =
  1.4, a gain ``(1 + w)`` of root-mean-square 1.72 on each of ``q`` and
  ``k``, so that the seeded scores have std about 2.9 and a query attends
  to a few keys, as a trained layer does (``nemotron_h``'s finding,
  PERF.md section 4: at std 1 the softmax over up to 4,096 seeded keys is
  nearly uniform and its output all cancellation; here ``q`` and ``k`` are
  normed a head, so the gain can only come from the norms);
* ``A_log`` and ``dt_bias`` are zero-mean normals of std 2 (the harness
  draws no other law): ``g = -exp(A_log) softplus(a + dt_bias)`` then gives
  per-token decays from nearly 1 to nearly 0 over the 32 value heads, so
  some heads forget within a token and some carry a state across chunks;
  the gated norm's ``w_n`` is ones and the convolution's taps have std
  ``taps ** -0.5``.
"""
from __future__ import annotations

import jax.numpy as jnp

LORA_B_STD = 0.02
EMBED_STD = 1.0
HEAD_STD = 0.02
ROUTER_STD = 1.0
QK_NORM_STD = 1.4
SSM_STD = 2.0   # A_log, dt_bias


def _dense(out, cfg, name, i, o, gain=1.0, adapters=True):
    rank = cfg["run"]["lora_rank"]
    leaf = name.split("/")[-1]
    out[f"{name}/kernel"] = ((i, o), jnp.bfloat16, gain * i ** -0.5)
    if adapters and leaf in cfg["run"]["lora_targets"]:
        out[f"{name}/lora_a"] = ((i, rank), jnp.float32, i ** -0.5)
        out[f"{name}/lora_b"] = ((rank, o), jnp.float32, LORA_B_STD)


def is_linear(cfg: dict, layer: int) -> bool:
    return (layer + 1) % cfg["full_attention_interval"] != 0


def layer_specs(cfg: dict, layer: int) -> dict:
    """Leaf names within layer ``layer``."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    hid = cfg["hidden_size"]
    branch = (8 * cfg["num_hidden_layers"]) ** -0.5
    out = {"input_norm/weight": ((hid,), f32, 0.0),
           "post_attn_norm/weight": ((hid,), f32, 0.0)}
    if is_linear(cfg, layer):
        hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
        dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
        taps, conv = cfg["linear_conv_kernel_dim"], 2 * hk * dk + hv * dv
        _dense(out, cfg, "linear_attn/in_proj_qkvz", hid,
               2 * hk * dk + 2 * hv * dv)
        _dense(out, cfg, "linear_attn/in_proj_ba", hid, 2 * hv,
               adapters=False)
        _dense(out, cfg, "linear_attn/out_proj", hv * dv, hid, branch)
        out.update({
            "linear_attn/conv_kernel": ((taps, conv), f32, taps ** -0.5),
            "linear_attn/dt_bias": ((hv,), f32, SSM_STD),
            "linear_attn/A_log": ((hv,), f32, SSM_STD),
            "linear_attn/gate_norm_scale": ((dv,), f32, None)})
    else:
        d = cfg["head_dim"]
        q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
        for name, (i, o, gain) in {
                "q_proj": (hid, 2 * q, 1.0), "k_proj": (hid, kv, 1.0),
                "v_proj": (hid, kv, 1.0), "o_proj": (q, hid, branch)}.items():
            _dense(out, cfg, f"attn/{name}", i, o, gain)
        out.update({"attn/q_norm/weight": ((d,), f32, QK_NORM_STD),
                    "attn/k_norm/weight": ((d,), f32, QK_NORM_STD)})
    held, total = cfg["num_experts"], cfg["published"]["num_experts"]
    mid, wide = (cfg["moe_intermediate_size"],
                 cfg["shared_expert_intermediate_size"])
    out.update({
        "moe/router_weight": ((hid, total), f32, ROUTER_STD * hid ** -0.5),
        "moe/experts/gate_proj": ((held, hid, mid), bf16, hid ** -0.5),
        "moe/experts/up_proj": ((held, hid, mid), bf16, hid ** -0.5),
        "moe/experts/down_proj": ((held, mid, hid), bf16,
                                  branch * mid ** -0.5)})
    _dense(out, cfg, "moe/shared/gate_proj", hid, wide, adapters=False)
    _dense(out, cfg, "moe/shared/up_proj", hid, wide, adapters=False)
    _dense(out, cfg, "moe/shared/down_proj", wide, hid, branch,
           adapters=False)
    _dense(out, cfg, "moe/shared_gate", hid, 1, adapters=False)
    return out


def top_specs(cfg: dict) -> dict:
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    return {"embed_tokens": ((v, h), jnp.bfloat16, EMBED_STD),
            "final_norm/scale": ((h,), jnp.float32, None),
            "lm_head": ((h, v), jnp.bfloat16, HEAD_STD)}


def is_trainable(path: str) -> bool:
    return "lora_" in path
