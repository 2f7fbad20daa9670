"""The leaves of ``LongcatFlashForCausalLM`` by their flax names, from the
configuration alone: ``{name: (shape, dtype, std)}``; std None = ones. A
layer is a double layer: two latent attentions (``attn_0``, ``attn_1``),
two dense FFNs (``mlp_0``, ``mlp_1``), four norms and the expert branch
(``moe``).

As the other families': matrices a token is multiplied by are bfloat16
with a fan-in std, adapters and norm scales float32, ``lora_b`` NOT zero.
What is scaled for this family, and why (the configuration's ``assumed``
says the same):

* the embedding has std 1 and what a branch adds to the residual stream is
  scaled by ``(8 x layers) ** -0.5`` (both ``o_proj``, both dense
  ``down_proj`` and the experts' ``down_proj``: five branches a double
  layer): a seeded router spreads its tokens only while what every token
  shares stays small beside what tells tokens apart; the untied head keeps
  ``llama``'s std 0.02;
* the router's logits have std ``ROUTER_STD`` (1) over its 768 outputs;
* the selection bias ``b`` is seeded at std ``B_SEL_STD`` = 1e-4. The
  softmax's probabilities over 768 outputs are small (1.7e-3 of spread,
  1e-2 at the chosen twelve), so ``glm4_moe_lite``'s 0.005 would pick in
  their place: on logits of std 1 it moves 69 % of the choices and the
  busiest output takes 64 times the mean load; at 1e-4 it moves 2 % of
  them, at near-ties, and the load stays what it is without it (a draw of
  4,096 tokens in numpy);
* ``q_b_proj`` carries a gain ``Q_GAIN`` that gives the seeded scores a std
  of ``SCORE_STD`` = 2.9, so a query attends to a few keys, as a trained
  layer does (``glm4_moe_lite``'s remedy). Here the model's own latent
  scales already widen the scores: ``q`` is times ``sqrt(hidden /
  q_lora_rank)`` and the normed kv latent times ``sqrt(hidden /
  kv_lora_rank)``, so at a gain of 1 the scores' std would be ``2 x
  sqrt((128 x 12 + 64) / 192)`` = 5.8 and the gain is about 0.5.
"""
from __future__ import annotations

import math

import jax.numpy as jnp

LORA_B_STD = 0.02
EMBED_STD = 1.0
HEAD_STD = 0.02
ROUTER_STD = 1.0
SCORE_STD = 2.9
B_SEL_STD = 1e-4


def _dense(out, cfg, name, i, o, gain=1.0, adapters=True):
    rank = cfg["run"]["lora_rank"]
    leaf = name.split("/")[-1]
    out[f"{name}/kernel"] = ((i, o), jnp.bfloat16, gain * i ** -0.5)
    if adapters and leaf in cfg["run"]["lora_targets"]:
        out[f"{name}/lora_a"] = ((i, rank), jnp.float32, i ** -0.5)
        out[f"{name}/lora_b"] = ((rank, o), jnp.float32, LORA_B_STD)


def q_gain(cfg: dict) -> float:
    """The gain on ``q_b_proj`` that gives seeded scores a std of
    ``SCORE_STD``, past the model's own latent scales."""
    hid, nope, rope = (cfg["hidden_size"], cfg["qk_nope_head_dim"],
                       cfg["qk_rope_head_dim"])
    q = math.sqrt(hid / cfg["q_lora_rank"])
    kv = math.sqrt(hid / cfg["kv_lora_rank"])
    return SCORE_STD / (q * math.sqrt((nope * kv * kv + rope) / (nope + rope)))


def layer_specs(cfg: dict, layer: int) -> dict:
    """Leaf names within double layer ``layer``."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    hid, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    q_lat, kv_lat = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    branch = (8 * cfg["num_layers"]) ** -0.5
    ffn = cfg["ffn_hidden_size"]
    out = {}
    for i in (0, 1):
        out.update({f"input_norm_{i}/scale": ((hid,), f32, None),
                    f"post_attn_norm_{i}/scale": ((hid,), f32, None),
                    f"attn_{i}/q_a_norm/scale": ((q_lat,), f32, None),
                    f"attn_{i}/kv_a_norm/scale": ((kv_lat,), f32, None)})
        for name, (n_in, n_out, gain) in {
                "q_a_proj": (hid, q_lat, 1.0),
                "q_b_proj": (q_lat, h * (nope + rope), q_gain(cfg)),
                "kv_a_proj": (hid, kv_lat + rope, 1.0),
                "kv_b_proj": (kv_lat, h * (nope + dv), 1.0),
                "o_proj": (h * dv, hid, branch)}.items():
            _dense(out, cfg, f"attn_{i}/{name}", n_in, n_out, gain)
        _dense(out, cfg, f"mlp_{i}/gate_proj", hid, ffn, adapters=False)
        _dense(out, cfg, f"mlp_{i}/up_proj", hid, ffn, adapters=False)
        _dense(out, cfg, f"mlp_{i}/down_proj", ffn, hid, branch,
               adapters=False)
    held, mid = cfg["n_routed_experts"], cfg["expert_ffn_hidden_size"]
    outputs = cfg["published"]["n_routed_experts"] + cfg["zero_expert_num"]
    out.update({
        "moe/router_weight": ((hid, outputs), f32, ROUTER_STD * hid ** -0.5),
        "moe/router_bias": ((outputs,), f32, B_SEL_STD),
        "moe/experts/gate_proj": ((held, hid, mid), bf16, hid ** -0.5),
        "moe/experts/up_proj": ((held, hid, mid), bf16, hid ** -0.5),
        "moe/experts/down_proj": ((held, mid, hid), bf16,
                                  branch * mid ** -0.5)})
    return out


def top_specs(cfg: dict) -> dict:
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    return {"embed_tokens": ((v, h), jnp.bfloat16, EMBED_STD),
            "final_norm/scale": ((h,), jnp.float32, None),
            "lm_head": ((h, v), jnp.bfloat16, HEAD_STD)}


def is_trainable(path: str) -> bool:
    return "lora_" in path
