"""The leaves of ``GlmMoeLiteForCausalLM`` by their flax names, from the
configuration alone: ``{name: (shape, dtype, std)}``; std None = ones.
Layer ``i`` is dense before ``first_k_dense_replace`` and an expert layer
after.

As the other families': matrices a token is multiplied by are bfloat16
with a fan-in std, adapters and norm scales float32, ``lora_b`` NOT zero.
What is scaled for this family, and why (the configuration's ``assumed``
says the same):

* the embedding has std 1 and what a branch adds to the residual stream is
  scaled by ``(8 x layers) ** -0.5`` (``o_proj`` and every ``down_proj``: the
  dense MLP's, the experts', the shared expert's; a layer has two
  branches, so this is ``nemotron_h``'s ``(4 x layers) ** -0.5`` a branch):
  a seeded router spreads its tokens only while what every token shares
  stays small beside what tells tokens apart; the untied head keeps
  ``llama``'s std 0.02;
* the router's scores are ``sigmoid`` of logits of std ``ROUTER_STD`` (1:
  the scores spread over most of (0, 1)); the selection bias ``b_sel`` IS
  seeded, at std ``B_SEL_STD`` = 0.005, small beside the scores' spread: a
  trained one balances the load, a seeded one can only unbalance it, so it
  is kept large enough to move choices at near-ties (the path is
  exercised) and small enough to move an expert's load little;
* ``q_b_proj`` carries a gain of ``Q_GAIN`` = 2.9, so the scores have std
  2.9 and a query attends to a few keys, as a trained layer does. At a gain
  of 1 the softmax over up to 4,096 seeded keys is nearly uniform, its
  output is the mean of the values (all cancellation), and the gradient of
  ``o_proj``'s adapter is then the least well conditioned number of the
  round (``nemotron_h``'s finding, PERF.md section 4: there the gain is 1.7 on
  each of q and k; here keys and values come from ONE product, so the
  whole gain sits on the query's).
"""
from __future__ import annotations

import jax.numpy as jnp

LORA_B_STD = 0.02
EMBED_STD = 1.0
HEAD_STD = 0.02
ROUTER_STD = 1.0
Q_GAIN = 2.9
B_SEL_STD = 0.005


def _dense(out, cfg, name, i, o, gain=1.0, adapters=True):
    rank = cfg["run"]["lora_rank"]
    leaf = name.split("/")[-1]
    out[f"{name}/kernel"] = ((i, o), jnp.bfloat16, gain * i ** -0.5)
    if adapters and leaf in cfg["run"]["lora_targets"]:
        out[f"{name}/lora_a"] = ((i, rank), jnp.float32, i ** -0.5)
        out[f"{name}/lora_b"] = ((rank, o), jnp.float32, LORA_B_STD)


def _swiglu(out, cfg, name, width, branch):
    hid = cfg["hidden_size"]
    _dense(out, cfg, f"{name}/gate_proj", hid, width, adapters=False)
    _dense(out, cfg, f"{name}/up_proj", hid, width, adapters=False)
    _dense(out, cfg, f"{name}/down_proj", width, hid, branch, adapters=False)


def is_dense(cfg: dict, layer: int) -> bool:
    return layer < cfg["first_k_dense_replace"]


def layer_specs(cfg: dict, layer: int) -> dict:
    """Leaf names within layer ``layer``."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    hid, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    q_lat, kv_lat = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    branch = (8 * cfg["num_hidden_layers"]) ** -0.5
    out = {"input_norm/scale": ((hid,), f32, None),
           "post_attn_norm/scale": ((hid,), f32, None),
           "attn/q_a_norm/scale": ((q_lat,), f32, None),
           "attn/kv_a_norm/scale": ((kv_lat,), f32, None)}
    for name, (i, o, gain) in {
            "q_a_proj": (hid, q_lat, 1.0),
            "q_b_proj": (q_lat, h * (nope + rope), Q_GAIN),
            "kv_a_proj": (hid, kv_lat + rope, 1.0),
            "kv_b_proj": (kv_lat, h * (nope + dv), 1.0),
            "o_proj": (h * dv, hid, branch)}.items():
        _dense(out, cfg, f"attn/{name}", i, o, gain)
    if is_dense(cfg, layer):
        _swiglu(out, cfg, "mlp", cfg["intermediate_size"], branch)
        return out
    e, mid = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    out.update({
        "moe/router_weight": ((hid, e), f32, ROUTER_STD * hid ** -0.5),
        "moe/router_bias": ((e,), f32, B_SEL_STD),
        "moe/experts/gate_proj": ((e, hid, mid), bf16, hid ** -0.5),
        "moe/experts/up_proj": ((e, hid, mid), bf16, hid ** -0.5),
        "moe/experts/down_proj": ((e, mid, hid), bf16, branch * mid ** -0.5)})
    _swiglu(out, cfg, "moe/shared", mid * cfg["n_shared_experts"], branch)
    return out


def top_specs(cfg: dict) -> dict:
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    return {"embed_tokens": ((v, h), jnp.bfloat16, EMBED_STD),
            "final_norm/scale": ((h,), jnp.float32, None),
            "lm_head": ((h, v), jnp.bfloat16, HEAD_STD)}


def is_trainable(path: str) -> bool:
    return "lora_" in path
