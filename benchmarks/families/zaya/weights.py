"""The leaves of ``ZayaForCausalLM`` by their flax names, from the
configuration alone: ``{name: (shape, dtype, std)}``; std None = ones.

As ``llama``'s: matrices a token is multiplied by are bfloat16 with a
fan-in std, adapters and norm scales float32, ``lora_b`` NOT zero, the
carried state's ``gamma`` and the key temperature one, biases a small std.
Three scales differ, all for one reason: a trained router spreads its
tokens over the experts, and a seeded one does only if what every token
shares stays small beside what tells tokens apart (the largest of 16
logits is decided by shifts of a tenth of their spread). With ``llama``'s
scales the busiest expert of a layer took 8-15 times the mean load, three
to eight experts of 16 got no token in a step, and the round's time
followed the seed (0.98 % spread over 6 seeds; PERF.md §4, §6):

* the embedding has std 1 (``llama``'s 0.02 is lost under the first
  sublayer's output, after which the tokens of a row are alike: cosine
  0.85 by layer 7) and what a sublayer adds to the residual stream is
  scaled by ``(2 x layers) ** -0.5`` (``o_proj``, ``down_proj``), the
  usual scaled init of output projections; the tied head's logits keep
  ``llama``'s spread through a final norm scale of std 0.02;
* the router MLP's two hidden matrices keep their pre-activations at std
  0.25, where gelu is nearly linear and its mean (which every token
  shares) is a tenth of its spread; the last matrix's gain brings the 16
  logits back to a spread of order one.

Read on the CPU at the cell's widths, 24 layers, two seeds: busiest expert
1.8-3.1 times the mean, all 16 experts live in every layer.
"""
from __future__ import annotations

import jax.numpy as jnp

LORA_B_STD = 0.02
EMBED_STD = 1.0
FINAL_NORM_STD = 0.02
BIAS_STD = 0.02
ROUTER_GAINS = (0.25, 2.0, 11.3)   # w1, w2, w3


def projections(cfg: dict) -> dict:
    """``{name: (in, out)}`` of the five attention projections."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    return {"q_proj": (h, q), "k_proj": (h, kv), "v_proj": (h, kv // 2),
            "v_prev_proj": (h, kv // 2), "o_proj": (q, h)}


def layer_specs(cfg: dict, layer: int) -> dict:
    """Leaf names within a layer; every layer is alike."""
    h, m, e = (cfg["hidden_size"], cfg["moe_intermediate_size"],
               cfg["num_experts"])
    d, r = cfg["head_dim"], cfg["router_hidden_size"]
    heads = cfg["num_attention_heads"] + cfg["num_key_value_heads"]
    rank = cfg["run"]["lora_rank"]
    f32, bf16 = jnp.float32, jnp.bfloat16
    branch = (2 * cfg["num_hidden_layers"]) ** -0.5
    out = {"input_norm/scale": ((h,), f32, None),
           "post_attn_norm/scale": ((h,), f32, None)}
    for name, (i, o) in projections(cfg).items():
        gain = branch if name == "o_proj" else 1.0
        out[f"attn/{name}/kernel"] = ((i, o), bf16, gain * i ** -0.5)
        if name in cfg["run"]["lora_targets"]:
            out[f"attn/{name}/lora_a"] = ((i, rank), f32, i ** -0.5)
            out[f"attn/{name}/lora_b"] = ((rank, o), f32, LORA_B_STD)
    t0, t1 = cfg["cca_time0"], cfg["cca_time1"]
    out.update({
        "attn/conv0_kernel": ((t0, heads * d), f32, t0 ** -0.5),
        "attn/conv0_bias": ((heads * d,), f32, BIAS_STD),
        "attn/conv1_kernel": ((t1, heads, d, d), bf16, (t1 * d) ** -0.5),
        "attn/conv1_bias": ((heads * d,), f32, BIAS_STD),
        "attn/k_temp": ((cfg["num_key_value_heads"],), f32, None),
        "moe/router_mlp/down": ((h, r), f32, h ** -0.5),
        "moe/router_mlp/gamma": ((r,), f32, None),
        "moe/router_mlp/norm_scale": ((r,), f32, None),
        "moe/router_mlp/w1": ((r, r), f32, ROUTER_GAINS[0] * r ** -0.5),
        "moe/router_mlp/w2": ((r, r), f32, ROUTER_GAINS[1] * r ** -0.5),
        "moe/router_mlp/w3": ((r, e), f32, ROUTER_GAINS[2] * r ** -0.5),
        "moe/experts/gate_proj": ((e, h, m), bf16, h ** -0.5),
        "moe/experts/up_proj": ((e, h, m), bf16, h ** -0.5),
        "moe/experts/down_proj": ((e, m, h), bf16, branch * m ** -0.5),
    })
    return out


def top_specs(cfg: dict) -> dict:
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    return {"embed_tokens": ((v, h), jnp.bfloat16, EMBED_STD),
            "final_norm/scale": ((h,), jnp.float32, FINAL_NORM_STD)}


def is_trainable(path: str) -> bool:
    return "lora_" in path
