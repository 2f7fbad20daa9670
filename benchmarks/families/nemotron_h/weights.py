"""The leaves of ``NemotronHForCausalLM`` by their flax names, from the
configuration alone: ``{name: (shape, dtype, std)}``; std None = ones.
Layer ``i`` is what letter ``i`` of ``hybrid_override_pattern`` says.

As the other families': matrices a token is multiplied by are bfloat16
with a fan-in std, adapters and norm scales float32, ``lora_b`` NOT zero.
What is scaled for this family, and why (the configuration's ``assumed``
says the same):

* the embedding has std 1 and what a mixer adds to the residual stream is
  scaled by ``(4 x layers) ** -0.5`` (``out_proj``, ``o_proj``, ``latent_out``,
  the shared expert's ``down_proj``), as ``zaya``'s: a seeded router
  spreads its tokens only while what every token shares stays small beside
  what tells tokens apart; the untied head keeps ``llama``'s std 0.02;
* the router's scores are ``sigmoid`` of logits of std ``ROUTER_STD``
  (1: the scores spread over most of (0, 1)); the selection bias ``b_sel``
  IS seeded, at std ``B_SEL_STD`` = 0.005, small beside the scores'
  spread: a trained one balances the load, a seeded one can only unbalance
  it, so it is kept large enough to move choices at near-ties (the path is
  exercised) and small enough to move an expert's load by about a tenth;
* the attention layer's ``q_proj`` and ``k_proj`` carry a gain of
  ``QK_GAIN`` = 1.7 each, so its scores have std 2.9 and a query attends
  to a few keys, as a trained layer does. Without rotary embedding and at
  std 1 the softmax over up to 2,048 seeded keys is nearly uniform, its
  output is the mean of the values (of size ``t ** -0.5``, all
  cancellation), and the gradient of ``o_proj``'s adapter is then the
  least well conditioned number of the round: sound runs read ``grad``
  0.003-0.005 on that leaf where the fp8 control read from 0.006 (8 seeds
  and 3 controls at std 1, PERF.md);
* ``A_log`` and ``dt_bias`` are zero-mean normals of std 2 (the harness
  draws no other law): ``a = -exp(A_log)`` and ``dt = softplus(dt_raw +
  dt_bias)`` then give per-token decays from 1e-3 to nearly 1 over the 128
  heads, so some heads forget within a token and some carry a state across
  many chunks; ``D`` and the gated norm's scale are ones, the convolution's
  taps have std ``taps ** -0.5`` and its bias std 0.02.
"""
from __future__ import annotations

import jax.numpy as jnp

LORA_B_STD = 0.02
EMBED_STD = 1.0
HEAD_STD = 0.02
BIAS_STD = 0.02
ROUTER_STD = 1.0
QK_GAIN = 1.7
B_SEL_STD = 0.005
SSM_STD = 2.0   # A_log, dt_bias


def _dense(out, cfg, name, i, o, gain=1.0, adapters=True):
    rank = cfg["run"]["lora_rank"]
    leaf = name.split("/")[-1]
    out[f"{name}/kernel"] = ((i, o), jnp.bfloat16, gain * i ** -0.5)
    if adapters and leaf in cfg["run"]["lora_targets"]:
        out[f"{name}/lora_a"] = ((i, rank), jnp.float32, i ** -0.5)
        out[f"{name}/lora_b"] = ((rank, o), jnp.float32, LORA_B_STD)


def layer_specs(cfg: dict, layer: int) -> dict:
    """Leaf names within layer ``layer``, by its letter of the pattern."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    hid = cfg["hidden_size"]
    branch = (4 * cfg["num_hidden_layers"]) ** -0.5
    out = {"input_norm/scale": ((hid,), f32, None)}
    kind = cfg["hybrid_override_pattern"][layer]
    if kind == "M":
        h, g, n = (cfg["mamba_num_heads"], cfg["n_groups"],
                   cfg["ssm_state_size"])
        d, taps = h * cfg["mamba_head_dim"], cfg["conv_kernel"]
        conv = d + 2 * g * n
        _dense(out, cfg, "mamba/in_proj", hid, d + conv + h)
        _dense(out, cfg, "mamba/out_proj", d, hid, branch)
        out.update({
            "mamba/conv_kernel": ((taps, conv), f32, taps ** -0.5),
            "mamba/conv_bias": ((conv,), f32, BIAS_STD),
            "mamba/dt_bias": ((h,), f32, SSM_STD),
            "mamba/A_log": ((h,), f32, SSM_STD),
            "mamba/D": ((h,), f32, None),
            "mamba/gate_norm_scale": ((d,), f32, None)})
    elif kind == "*":
        d = cfg["head_dim"]
        q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
        for name, (i, o, gain) in {
                "q_proj": (hid, q, QK_GAIN), "k_proj": (hid, kv, QK_GAIN),
                "v_proj": (hid, kv, 1.0), "o_proj": (q, hid, branch)}.items():
            _dense(out, cfg, f"attn/{name}", i, o, gain)
    elif kind == "E":
        lat, mid = cfg["moe_latent_size"], cfg["moe_intermediate_size"]
        held, total = cfg["n_routed_experts"], \
            cfg["published"]["n_routed_experts"]
        wide = cfg["moe_shared_expert_intermediate_size"]
        out.update({
            "moe/router_weight": ((hid, total), f32, ROUTER_STD * hid ** -0.5),
            "moe/router_bias": ((total,), f32, B_SEL_STD),
            "moe/experts/up_proj": ((held, lat, mid), bf16, lat ** -0.5),
            "moe/experts/down_proj": ((held, mid, lat), bf16, mid ** -0.5)})
        _dense(out, cfg, "moe/latent_in", hid, lat, adapters=False)
        _dense(out, cfg, "moe/latent_out", lat, hid, branch, adapters=False)
        _dense(out, cfg, "moe/shared/up_proj", hid, wide, adapters=False)
        _dense(out, cfg, "moe/shared/down_proj", wide, hid, branch,
               adapters=False)
    else:
        raise SystemExit(f"benchmark: nemotron_h layer {layer} is of kind "
                         f"{kind!r}: only M, * and E are implemented")
    return out


def top_specs(cfg: dict) -> dict:
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    return {"embed_tokens": ((v, h), jnp.bfloat16, EMBED_STD),
            "final_norm/scale": ((h,), jnp.float32, None),
            "lm_head": ((h, v), jnp.bfloat16, HEAD_STD)}


def is_trainable(path: str) -> bool:
    return "lora_" in path
