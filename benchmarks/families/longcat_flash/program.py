"""The program's model for ``model_type: longcat_flash``: what a user's
yaml names it by, its configuration object with every width as the
configuration's file has it, and its flax module (for the shape test)."""
from __future__ import annotations

# at import, so that a program without the family fails here, at once
from fedml_tpu.models.llm.longcat_flash import LongcatFlashConfig


def model_args(config: dict) -> dict:
    return {"model": "longcat_flash"}


def model_config(config: dict, traffic: dict):
    """``LongcatFlashConfig`` from the row's keys by their own names. The
    file's ``n_routed_experts`` is the number of FFN experts HELD here
    (``reduced``); the router's width is the published one, beside the
    identity experts."""
    import jax.numpy as jnp

    run = config["run"]
    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    taken = ("vocab_size", "hidden_size", "num_layers", "ffn_hidden_size",
             "num_attention_heads", "q_lora_rank", "kv_lora_rank",
             "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
             "mla_scale_q_lora", "mla_scale_kv_lora", "attention_method",
             "attention_bias", "max_position_embeddings", "n_routed_experts",
             "expert_ffn_hidden_size", "moe_topk", "zero_expert_num",
             "zero_expert_type")
    return LongcatFlashConfig(
        **{k: config[k] for k in taken},
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        rms_norm_eps=float(config["rms_norm_eps"]),
        rope_theta=float(config["rope_theta"]),
        n_routed_experts_total=config["published"]["n_routed_experts"],
        held_experts_first=run["held_experts_first"],
        lora_rank=run["lora_rank"], lora_alpha=run["lora_alpha"],
        dtype=dtypes[run["compute_dtype"]],
        param_dtype=dtypes[run["base_dtype"]],
        remat_policy=traffic["remat_policy"],
        use_flash=bool(run["use_flash_attention"]),
        moe_block_rows=run["moe_block_rows"])


def module(cfg):
    return cfg.module()
