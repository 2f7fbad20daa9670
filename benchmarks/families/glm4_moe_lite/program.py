"""The program's model for ``model_type: glm4_moe_lite``: what a user's
yaml names it by, its configuration object with every width as the
configuration's file has it, and its flax module (for the shape test)."""
from __future__ import annotations

# at import, so that a program without the family fails here, at once
from fedml_tpu.models.llm.glm_moe_lite import GlmMoeLiteConfig


def model_args(config: dict) -> dict:
    return {"model": "glm4_moe_lite"}


def model_config(config: dict, traffic: dict):
    """``GlmMoeLiteConfig`` from the row's keys by their own names."""
    import jax.numpy as jnp

    run = config["run"]
    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    taken = ("vocab_size", "hidden_size", "num_hidden_layers",
             "first_k_dense_replace", "intermediate_size",
             "num_attention_heads", "num_key_value_heads", "q_lora_rank",
             "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
             "v_head_dim", "attention_bias", "rope_scaling",
             "max_position_embeddings", "n_routed_experts",
             "num_experts_per_tok", "moe_intermediate_size",
             "n_shared_experts", "norm_topk_prob", "topk_method", "n_group",
             "topk_group", "hidden_act", "tie_word_embeddings")
    return GlmMoeLiteConfig(
        **{k: config[k] for k in taken},
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        rms_norm_eps=float(config["rms_norm_eps"]),
        rope_theta=float(config["rope_theta"]),
        lora_rank=run["lora_rank"], lora_alpha=run["lora_alpha"],
        dtype=dtypes[run["compute_dtype"]],
        param_dtype=dtypes[run["base_dtype"]],
        remat_policy=traffic["remat_policy"],
        use_flash=bool(run["use_flash_attention"]),
        moe_block_rows=run["moe_block_rows"])


def module(cfg):
    return cfg.module()
