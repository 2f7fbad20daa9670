"""The program's model for ``model_type: qwen3_next``: what a user's yaml
names it by, its configuration object with every width as the
configuration's file has it, and its flax module (for the shape test)."""
from __future__ import annotations

# at import, so that a program without the family fails here, at once
from fedml_tpu.models.llm.qwen3_next import Qwen3NextConfig


def model_args(config: dict) -> dict:
    return {"model": "qwen3_next"}


def model_config(config: dict, traffic: dict):
    """``Qwen3NextConfig`` from the row's keys by their own names. The
    file's ``num_experts`` is the number HELD here (``reduced``); the
    router's width is the published one."""
    import jax.numpy as jnp

    run = config["run"]
    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    taken = ("vocab_size", "hidden_size", "num_hidden_layers",
             "full_attention_interval", "decoder_sparse_step",
             "intermediate_size", "num_attention_heads",
             "num_key_value_heads", "head_dim", "rope_scaling",
             "use_sliding_window", "max_position_embeddings",
             "linear_num_key_heads", "linear_num_value_heads",
             "linear_key_head_dim", "linear_value_head_dim",
             "linear_conv_kernel_dim", "num_experts", "num_experts_per_tok",
             "moe_intermediate_size", "shared_expert_intermediate_size",
             "norm_topk_prob", "hidden_act", "tie_word_embeddings")
    return Qwen3NextConfig(
        **{k: config[k] for k in taken},
        mlp_only_layers=tuple(config["mlp_only_layers"]),
        partial_rotary_factor=float(config["partial_rotary_factor"]),
        rope_theta=float(config["rope_theta"]),
        rms_norm_eps=float(config["rms_norm_eps"]),
        num_experts_total=config["published"]["num_experts"],
        held_experts_first=run["held_experts_first"],
        lora_rank=run["lora_rank"], lora_alpha=run["lora_alpha"],
        dtype=dtypes[run["compute_dtype"]],
        param_dtype=dtypes[run["base_dtype"]],
        remat_policy=traffic["remat_policy"],
        use_flash=bool(run["use_flash_attention"]),
        moe_block_rows=run["moe_block_rows"])


def module(cfg):
    return cfg.module()
