"""The program's model for ``model_type: zaya``: what a user's yaml names
it by, its configuration object with every width as the configuration's
file has it, and its flax module (for the shape test)."""
from __future__ import annotations


def model_args(config: dict) -> dict:
    return {"model": "zaya"}


def model_config(config: dict, traffic: dict):
    """``ZayaConfig`` from the row's keys by their own names; the layers
    are all ``hybrid``, whose rope is ``rope_parameters.hybrid``."""
    import jax.numpy as jnp
    from fedml_tpu.models.llm.zaya import ZayaConfig

    run = config["run"]
    kinds = set(config["layer_types"])
    if kinds != {"hybrid"}:
        raise SystemExit(f"benchmark: zaya layers of kinds {sorted(kinds)}: "
                         "only 'hybrid' is implemented")
    rope = config["rope_parameters"]["hybrid"]
    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    taken = ("vocab_size", "hidden_size", "num_hidden_layers",
             "num_attention_heads", "num_key_value_heads", "head_dim",
             "cca_time0", "cca_time1", "num_experts", "num_experts_per_tok",
             "moe_intermediate_size", "router_hidden_size", "rms_norm_eps",
             "tie_word_embeddings", "attention_bias",
             "max_position_embeddings")
    return ZayaConfig(
        **{k: config[k] for k in taken},
        partial_rotary_factor=rope["partial_rotary_factor"],
        rope_theta=float(rope["rope_theta"]),
        lora_rank=run["lora_rank"], lora_alpha=run["lora_alpha"],
        dtype=dtypes[run["compute_dtype"]],
        param_dtype=dtypes[run["base_dtype"]],
        remat_policy=traffic["remat_policy"],
        use_flash=bool(run["use_flash_attention"]),
        moe_block_rows=run["moe_block_rows"])


def module(cfg):
    return cfg.module()
