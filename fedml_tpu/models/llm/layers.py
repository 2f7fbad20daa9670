"""What every causal-LM family of the LLM path is built from: RMSNorm,
rotary tables and their application, a dense layer with an optional
low-rank adapter (its base may be stored quantized), the choice of the
causal attention product, the SwiGLU of a dense FFN, and what the families
with routed experts share (a stack of expert matrices, the gated experts
over ``ops/grouped_matmul.py``, the sigmoid top-k router). A family's file
imports these and no other family's file."""
from __future__ import annotations

import math
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu.ops import grouped_matmul as gmm
from fedml_tpu.telemetry import get_tracer

HIGHEST = jax.lax.Precision.HIGHEST


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


def _half_swap(head_dim: int, rotary_dim: int) -> np.ndarray:
    """The rotate-half convention as a signed permutation ``P[D, D]``:
    ``(x @ P)[j] = -x[j + r]`` and ``(x @ P)[j + r] = x[j]`` for ``j < r``,
    ``r`` half of the rotary width; lanes past it get zero columns."""
    r = rotary_dim // 2
    j = np.arange(r)
    p = np.zeros((head_dim, head_dim), np.float32)
    p[j + r, j] = -1.0
    p[j, j + r] = 1.0
    return p


def _lanes(table: jax.Array, head_dim: int, rest: float) -> jax.Array:
    """``[..., T, r]`` -> ``[..., 1, T, D]``: the table over both halves,
    ``rest`` on the lanes past the rotary width, broadcast over heads."""
    pad = jnp.full(table.shape[:-1] + (head_dim - 2 * table.shape[-1],),
                   rest, table.dtype)
    return jnp.concatenate([table, table, pad], -1)[..., None, :, :]


def _swapped(x: jax.Array, rotary_dim: int, back: bool = False) -> jax.Array:
    """``x @ P`` in float32, or ``x @ P.T`` (``= -P``: the swap ``back``).
    One non-zero term a column, so the product is exact — given that an
    operand wider than bfloat16 is not rounded to it, which the MXU's
    default precision would do."""
    p = _half_swap(x.shape[-1], rotary_dim)
    p = jnp.asarray(p.T if back else p, x.dtype)
    exact = None if x.dtype == jnp.bfloat16 else jax.lax.Precision.HIGHEST
    return jnp.einsum("bhtd,de->bhte", x, p, precision=exact,
                      preferred_element_type=jnp.float32)


def _rotate(x, cos, sin, back: bool = False):
    """``x * [cos, cos, 1...] + (x @ P) * [sin, sin, 0...]`` in float32,
    rounded once to ``x.dtype``; ``back`` turns by the opposite angle. No
    split and no concatenation of ``x``'s minor dimension: those XLA does
    not fuse, and it keeps float32 copies of ``x`` and half-width arrays in
    HBM to feed them."""
    d = x.shape[-1]
    with jax.named_scope("rope"):
        y = (x.astype(jnp.float32) * _lanes(cos, d, 1.0)
             + _swapped(x, 2 * cos.shape[-1], back) * _lanes(sin, d, 0.0))
        return y.astype(x.dtype)


@jax.custom_vjp
def _rope(x, cos, sin):
    return _rotate(x, cos, sin)


def _rope_fwd(x, cos, sin):
    return _rotate(x, cos, sin), (x, cos, sin)


def _rope_bwd(res, g):
    """The rotation back, the same one pass over the same tables: the
    cotangent in, float32 multiply-adds, one rounding out. Autodiff of
    :func:`_rotate` would round the cotangent once a consumer of ``x`` and
    add the two in ``x.dtype``. The tables' cotangents are stated for
    whoever differentiates them; a training step does not, and they are
    dead code there."""
    x, cos, sin = res
    r = cos.shape[-1]
    with jax.named_scope("rope"):
        g32 = g.astype(jnp.float32)

        def table_grad(lanes):
            over = (0, 1) if cos.ndim == 2 else (1,)
            full = jnp.sum(g32 * lanes, axis=over)
            return (full[..., :r] + full[..., r:2 * r]).astype(cos.dtype)

        d_cos = table_grad(x.astype(jnp.float32))
        d_sin = table_grad(_swapped(x, 2 * r))
    return _rotate(g, cos, sin, back=True), d_cos, d_sin


# ``optimize_remat``: under ``nn.remat`` the forward rule stays one opaque
# call. Inlined, a policy that keeps products (``remat_policy: dots``) would
# keep this one's float32 output, twice ``x``'s bytes, to save a pass of
# elementwise work.
_rope.defvjp(_rope_fwd, _rope_bwd, optimize_remat=True)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array):
    """x: ``[B, H, T, D]``, whole heads; cos/sin: ``[B, T, r]`` or
    ``[T, r]``. The rotary width is the tables' (``2 r``): lanes of a head
    past it pass through unchanged."""
    b, h, t, d = x.shape
    get_tracer().event(
        "rope/plan", rows=b * t, heads=h, head_dim=d,
        rotary_dim=2 * cos.shape[-1], dtype=jnp.dtype(x.dtype).name,
        form="product")
    return _rope(x, cos, sin)


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


class SwiGLU(nn.Module):
    """``(silu(u W_g) * (u W_u)) W_d`` at ``width``: a dense layer's FFN or
    a shared expert. Frozen under LoRA: the adapters sit on the attention
    projections."""

    cfg: Any
    width: int

    @nn.compact
    def __call__(self, u):
        cfg, up_axes = self.cfg, ("embed", "mlp")
        gate = lora_dense(cfg, self.width, "gate_proj", up_axes,
                          adapters=False)(u)
        up = lora_dense(cfg, self.width, "up_proj", up_axes,
                        adapters=False)(u)
        return lora_dense(cfg, cfg.hidden_size, "down_proj", ("mlp", "embed"),
                          adapters=False)(nn.silu(gate) * up)


def expert_stack(module, cfg, count: int, name: str, shape, in_axis: str,
                 out_axis: str) -> jax.Array:
    """``count`` experts' matrices ``name`` as one parameter of ``module``,
    ``[count, *shape]`` with the leading dimension on the mesh's expert
    axis, in the compute type."""
    return module.param(
        name, nn.with_logical_partitioning(
            nn.initializers.lecun_normal(), ("expert", in_axis, out_axis)),
        (count, *shape), cfg.param_dtype).astype(cfg.dtype)


class GatedExperts(nn.Module):
    """``(silu(x G_e) * (x U_e)) D_e`` for ``experts`` experts of
    ``cfg.moe_intermediate_size``, over rows already sorted by expert:
    three grouped products, the SwiGLU between them outside the kernels."""

    cfg: Any
    experts: int

    @nn.compact
    def __call__(self, xs, layout):
        cfg = self.cfg
        hid, mid = cfg.hidden_size, cfg.moe_intermediate_size
        stack = lambda name, shape, *axes: expert_stack(
            self, cfg, self.experts, name, shape, *axes)
        product = lambda a, w: gmm.grouped_matmul(
            a, w, layout, block_m=cfg.moe_block_rows)
        gate = product(xs, stack("gate_proj", (hid, mid), "embed", "mlp"))
        up = product(xs, stack("up_proj", (hid, mid), "embed", "mlp"))
        return product(nn.silu(gate) * up,
                       stack("down_proj", (mid, hid), "mlp", "embed"))


def sigmoid_topk(module, u, total: int, k: int, scale: float):
    """A token's ``k`` of ``total`` experts by sigmoid scores with a
    selection bias (``noaux_tc``), under the scope ``router``; the float32
    parameters ``router_weight`` ``[hidden, total]`` and ``router_bias``
    are ``module``'s. ``chosen [m, k]`` = the largest of ``score + bias``
    (the bias picks, it does not weigh) and ``weights [m, total]`` = the
    chosen experts' scores over their sum times ``scale``, zero elsewhere.
    The weights stay a dense table: no gather of scalars on the way in, no
    scatter-add on the way back."""
    hid = u.shape[-1]
    gate = module.param(
        "router_weight", nn.with_logical_partitioning(
            nn.initializers.normal(1.0 / math.sqrt(hid)), ("embed", None)),
        (hid, total), jnp.float32)
    bias = module.param("router_bias", nn.initializers.zeros, (total,),
                        jnp.float32)
    with jax.named_scope("router"):
        # float32 at full precision: a rounded score is a token sent
        # to another expert
        scores = jax.nn.sigmoid(jnp.matmul(
            u.reshape(-1, hid).astype(jnp.float32), gate,
            precision=HIGHEST))
        _, chosen = jax.lax.top_k(
            jax.lax.stop_gradient(scores) + bias, k)              # [m, k]
        chosen = chosen.astype(jnp.int32)
        picked = jnp.any(
            chosen[:, :, None] == jnp.arange(total, dtype=jnp.int32),
            axis=1)
        kept = jnp.where(picked, scores, 0.0)
        weights = kept / (jnp.sum(kept, -1, keepdims=True) + 1e-20) * scale
    return chosen, weights


def choice_weights(chosen, weights, first: int, held: int):
    """``[m, k]``: each choice's weight out of :func:`sigmoid_topk`'s
    table, zero for an expert outside ``first .. first + held`` (held on
    another chip)."""
    own = (chosen[:, :, None] - first
           == jnp.arange(held, dtype=jnp.int32))                # [m, k, Eh]
    return jnp.sum(
        jnp.where(own, weights[:, None, first:first + held], 0.0), axis=2)
