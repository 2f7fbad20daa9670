"""The program's model for ``model_type: nemotron_h``: what a user's yaml
names it by, its configuration object with every width as the
configuration's file has it, and its flax module (for the shape test)."""
from __future__ import annotations

# at import, so that a program without the family fails here, at once
from fedml_tpu.models.llm.nemotron_h import NemotronHConfig


def model_args(config: dict) -> dict:
    return {"model": "nemotron_h"}


def model_config(config: dict, traffic: dict):
    """``NemotronHConfig`` from the row's keys by their own names. The
    file's ``n_routed_experts`` is the number HELD here (``reduced``); the
    router's width is the published one."""
    import jax.numpy as jnp

    run = config["run"]
    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    taken = ("vocab_size", "hidden_size", "num_hidden_layers",
             "hybrid_override_pattern", "num_attention_heads",
             "num_key_value_heads", "head_dim", "attention_bias",
             "mamba_num_heads", "mamba_head_dim", "n_groups",
             "ssm_state_size", "conv_kernel", "chunk_size", "use_conv_bias",
             "mamba_proj_bias", "n_routed_experts", "num_experts_per_tok",
             "moe_intermediate_size", "moe_latent_size",
             "moe_shared_expert_intermediate_size", "n_shared_experts",
             "norm_topk_prob", "n_group", "topk_group", "mlp_bias",
             "tie_word_embeddings", "max_position_embeddings")
    return NemotronHConfig(
        **{k: config[k] for k in taken},
        n_routed_experts_total=config["published"]["n_routed_experts"],
        held_experts_first=run["held_experts_first"],
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        rms_norm_eps=float(config["layer_norm_epsilon"]),
        rope_theta=float(config["rope_theta"]),
        lora_rank=run["lora_rank"], lora_alpha=run["lora_alpha"],
        dtype=dtypes[run["compute_dtype"]],
        param_dtype=dtypes[run["base_dtype"]],
        remat_policy=traffic["remat_policy"],
        use_flash=bool(run["use_flash_attention"]),
        moe_block_rows=run["moe_block_rows"])


def module(cfg):
    return cfg.module()
