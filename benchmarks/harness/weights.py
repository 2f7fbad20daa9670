"""The model's weights, made on the device from ``--seed``.

The benchmark makes the weights, not the program: the program's
constructor draws its own (``engine.init``), which the harness frees and
replaces, so that the reference can make the same values again without
taking anything from the program. ``leaf_specs`` states the layout (the
flax names of ``LlamaForCausalLM``) from the configuration alone; the
harness checks the program's tree against it before installing.

Every leaf is ``normal(key(seed, path)) * scale`` in the type it is
trained in: base kernels and embeddings bfloat16, adapters and norm scales
float32. ``lora_b`` is NOT zero (std 0.02): the window measures a
federation in progress, and with B = 0 the gradient of every ``lora_a`` is
exactly zero, which would leave half of the adapter path unchecked.
One jitted call per layer (the same program for every layer) and one for
the embeddings, the head and the final norm.
"""
from __future__ import annotations

import functools
import zlib

import jax
import jax.numpy as jnp

LORA_TARGETS = ("q_proj", "k_proj", "v_proj", "o_proj")
MLP = ("gate_proj", "up_proj", "down_proj")
LORA_B_STD = 0.02
EMBED_STD = 0.02


def layer_specs(cfg: dict) -> dict:
    """``{leaf name within a layer: (shape, dtype, std)}``; std None = ones."""
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    d = h // cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"] * d
    r = cfg["run"]["lora_rank"]
    out = {"input_norm/scale": ((h,), jnp.float32, None),
           "post_attn_norm/scale": ((h,), jnp.float32, None)}
    for name, (i, o) in {"q_proj": (h, h), "k_proj": (h, kv),
                         "v_proj": (h, kv), "o_proj": (h, h)}.items():
        out[f"attn/{name}/kernel"] = ((i, o), jnp.bfloat16, i ** -0.5)
        if name in cfg["run"]["lora_targets"]:
            out[f"attn/{name}/lora_a"] = ((i, r), jnp.float32, i ** -0.5)
            out[f"attn/{name}/lora_b"] = ((r, o), jnp.float32, LORA_B_STD)
    for name, (i, o) in {"gate_proj": (h, f), "up_proj": (h, f),
                         "down_proj": (f, h)}.items():
        out[f"mlp/{name}/kernel"] = ((i, o), jnp.bfloat16, i ** -0.5)
    return out


def top_specs(cfg: dict) -> dict:
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    out = {"embed_tokens": ((v, h), jnp.bfloat16, EMBED_STD),
           "final_norm/scale": ((h,), jnp.float32, None)}
    if not cfg["tie_word_embeddings"]:
        out["lm_head"] = ((h, v), jnp.bfloat16, EMBED_STD)
    return out


def leaf_specs(cfg: dict) -> dict:
    """Every leaf of the model by its path below ``params/``."""
    out = dict(top_specs(cfg))
    per_layer = layer_specs(cfg)
    for i in range(cfg["num_hidden_layers"]):
        out.update({f"layer_{i}/{k}": v for k, v in per_layer.items()})
    return out


def seed_key(seed: int):
    """A key for any non-negative whole ``seed`` (also past 2**32)."""
    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def _leaf(key, name: str, shape, dtype, std):
    if std is None:
        return jnp.ones(shape, dtype)
    k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)


def _freeze(specs: dict):
    return tuple((k, tuple(s), jnp.dtype(d).name, std)
                 for k, (s, d, std) in sorted(specs.items()))


@functools.partial(jax.jit, static_argnums=(2,))
def _make(key, index, specs):
    key = jax.random.fold_in(key, index)
    return {name: _leaf(key, name, shape, jnp.dtype(dtype), std)
            for name, shape, dtype, std in specs}


def make_layer(cfg: dict, seed: int, layer: int) -> dict:
    """Layer ``layer``'s leaves by their name within the layer."""
    return _make(seed_key(seed), layer + 1, _freeze(layer_specs(cfg)))


def make_top(cfg: dict, seed: int) -> dict:
    return _make(seed_key(seed), 0, _freeze(top_specs(cfg)))


def make_all(cfg: dict, seed: int) -> dict:
    """``{path below params/: array}`` for the whole model."""
    out = dict(make_top(cfg, seed))
    for i in range(cfg["num_hidden_layers"]):
        out.update({f"layer_{i}/{k}": v
                    for k, v in make_layer(cfg, seed, i).items()})
    return out


def is_lora(path: str) -> bool:
    return "lora_" in path


def param_count(cfg: dict, lora: bool = False) -> int:
    n = 0
    for path, (shape, _, _) in leaf_specs(cfg).items():
        if is_lora(path) == lora:
            size = 1
            for s in shape:
                size *= s
            n += size
    return n
