"""What every causal-LM family of the LLM path is built from: RMSNorm,
rotary tables and their application, a dense layer with an optional
low-rank adapter (its base may be stored quantized), and the choice of the
causal attention product. A family's file imports these and no other
family's file."""
from __future__ import annotations

from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp


class RMSNorm(nn.Module):
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],), jnp.float32)
        x32 = x.astype(jnp.float32)
        normed = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + self.eps)
        return (normed * scale).astype(self.dtype)


def rope_tables(positions: jax.Array, head_dim: int, theta: float):
    """cos/sin tables for rotary embeddings; positions [B, T] or [T]."""
    freqs = 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    angles = positions.astype(jnp.float32)[..., None] * freqs  # [..., T, D/2]
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array):
    """x: [B, H, T, D]; cos/sin: [B, T, D/2] or [T, D/2]."""
    with jax.named_scope("rope"):
        x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
        if cos.ndim == 2:
            cos, sin = cos[None, None], sin[None, None]
        else:
            cos, sin = cos[:, None], sin[:, None]
        return jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
        ).astype(x.dtype)


def _maybe_packed_param(module, name, init_box, shape, dtype):
    """``self.param``, except a 4-bit packed kernel is read straight from
    the variable dict.

    Flax's param path leaf-compares the stored value against the
    initializer's eval_shape; an int8 :class:`QuantizedTensor` passes
    (its data keeps the kernel shape) but a :class:`QuantizedTensor4`
    legitimately differs — packed nibbles are ``[n_blocks, block//2]``.
    The packed base is frozen (never initialized, never differentiated),
    so skipping the shape check loses nothing.
    """
    from fedml_tpu.ops.quant import QuantizedTensor4

    scope = module.scope
    if scope.has_variable("params", name):
        v = scope.get_variable("params", name)
        # raw model.init params keep flax partitioning boxes; the packed
        # value may live inside one (the trainer stores unboxed)
        if isinstance(v, nn.meta.AxisMetadata):
            v = v.unbox()
        if isinstance(v, QuantizedTensor4):
            return v
    return module.param(name, init_box, shape, dtype)


class LoRADense(nn.Module):
    """Dense with optional additive low-rank adapter: y = xW + (x A) B * s.

    The base kernel is a normal flax param (frozen by the LLM optimizer
    mask); ``lora_a/lora_b`` live under the same params tree with a
    ``lora_`` name prefix, which is what the trainable/exchange filters key
    on (``fedml_tpu/train/llm/federated.py``).
    """

    features: int
    rank: int = 0
    alpha: float = 16.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32  # base kernel storage; lora_a/b stay fp32
    kernel_axes: Tuple[str, ...] = ()

    @nn.compact
    def __call__(self, x):
        kernel = _maybe_packed_param(
            self,
            "kernel",
            nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), self.kernel_axes
            ),
            (x.shape[-1], self.features),
            self.param_dtype,
        )
        from fedml_tpu.ops.quant import matmul_maybe_quantized

        y = matmul_maybe_quantized(x, kernel, self.dtype)
        if self.rank > 0:
            a = self.param(
                "lora_a",
                nn.with_logical_partitioning(
                    nn.initializers.lecun_normal(),
                    (self.kernel_axes[0] if self.kernel_axes else None, None),
                ),
                (x.shape[-1], self.rank),
                jnp.float32,
            )
            b = self.param(
                "lora_b",
                nn.with_logical_partitioning(
                    nn.initializers.zeros,
                    (None, self.kernel_axes[1] if len(self.kernel_axes) > 1 else None),
                ),
                (self.rank, self.features),
                jnp.float32,
            )
            scaling = self.alpha / self.rank
            y = y + (x @ a.astype(self.dtype)) @ b.astype(self.dtype) * scaling
        return y


def lora_dense(cfg, feats: int, name: str, axes: Tuple[str, ...],
               adapters: bool = True) -> LoRADense:
    """The projection ``name`` of ``cfg``'s model: compute and storage types
    from ``cfg``, and its adapter unless ``adapters`` is off (an MLP's
    projections carry none)."""
    return LoRADense(
        feats, rank=cfg.lora_rank if adapters else 0, alpha=cfg.lora_alpha,
        dtype=cfg.dtype, param_dtype=cfg.param_dtype, kernel_axes=axes,
        name=name,
    )


def causal_attention(q, k, v, cfg, attention_fn=None):
    """Causal softmax attention over ``[B, H, T, D]`` heads: through
    ``attention_fn`` where the trainer gives one (the flash kernel per
    shard of its mesh, or the ring over ``sp``), else the flash kernel,
    else (``cfg.use_flash`` off) plain XLA."""
    if attention_fn is not None:
        return attention_fn(q, k, v)
    if cfg.use_flash:
        from fedml_tpu.ops.flash_attention import flash_attention

        return flash_attention(q, k, v, causal=True)
    from fedml_tpu.ops.flash_attention import reference_attention

    return reference_attention(q, k, v, causal=True)


def merge_heads(out: jax.Array) -> jax.Array:
    """``[B, H, T, D]`` -> ``[B, T, H * D]``, what ``o_proj`` takes."""
    b, h, t, d = out.shape
    # flax names the projections; what is not a module gets a scope of
    # its own, so a device trace can tell the glue from the matmuls
    with jax.named_scope("attn_layout"):
        return out.transpose(0, 2, 1, 3).reshape(b, t, h * d)
