"""Int8 and 4-bit weight-only quantization for TPU serving/training.

Quantizes 2-D kernels to per-output-channel int8 and swaps them into the
params pytree as :class:`QuantizedTensor` leaves; ``LoRADense`` / the lm
head consume them as ``(x @ q.astype(bf16)) * scale`` — mathematically
identical to dequantize-then-matmul with the scale folded into outputs.

:class:`QuantizedTensor4` is the 4-bit sibling (QLoRA, Dettmers et al.
2023): blockwise int4 or NF4 codes packed two per uint8 plus one f32
absmax scale per block — the same packing layout as the ``int4``/``nf4``
wire codec, so HBM holds exactly the wire bytes (~0.27× of bf16). The
dequant is fused into whatever program consumes the matmul: inside a
trace (the fused round, serving prefill/decode) the unpacked bf16 tile
is an XLA temporary, and the eager path routes through the cataloged
``quant/dequant_matmul`` program — a full-precision copy of the base is
never resident.

What it buys (measured on-chip, PERF_NOTES round-4 addendum): **HBM
residency halves** (2.25 GB → 1.13 GB for the 1.1B bench model) AND,
with the default Pallas fused dequant-matmul, **decode gets 1.7× faster**
(3.14 ms vs 5.38 ms bf16 at B8/ctx512 → 2548 vs 1486 tok/s). The fusion
XLA refuses — it materializes the int8→bf16 convert, which is why the
plain lowering measured *slower* than bf16 (8.0 ms) — is done by hand in
``pallas_dequant_matmul``: weight tiles stream from HBM as int8 and
convert in-VMEM. ``w8a8`` (int8×int8 MXU dot) also loses under XLA's
lowering (6.8 ms); the kernel wins on pure weight bandwidth.

No reference counterpart: the reference delegates quantized serving to
vLLM/Triton containers.
"""
from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fedml_tpu.ops.dispatch import INTERPRET, kernel_mode


@jax.tree_util.register_pytree_node_class
class QuantizedTensor:
    """Per-output-channel symmetric int8 weight: ``w ≈ data * scale``.

    ``mode`` selects the matmul lowering:
      * ``"dequant"`` — x·dequant(W) in bf16 (exact w.r.t. the quantized
        weights; XLA materializes the int8→bf16 convert, so it buys HBM
        capacity but not decode latency);
      * ``"w8a8"``    — dynamic per-row activation quant + int8×int8 dot
        accumulated in int32 (``preferred_element_type``), MXU-native;
      * ``"pallas"``  — fused dequant-matmul kernel: weight tiles DMA'd
        from HBM as int8 and converted in-VMEM (half the weight
        bandwidth — the decode-latency path). bf16-activation-only:
        exact for bf16 compute; fp32 requests fall back to "dequant".
    """

    def __init__(self, data, scale, mode: str = "dequant"):
        self.data = data    # int8  [in, out]
        self.scale = scale  # f32   [out]
        self.mode = mode

    # -- pytree protocol ------------------------------------------------
    def tree_flatten(self):
        return (self.data, self.scale), self.mode

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, mode=aux)

    # -- array-ish surface ----------------------------------------------
    @property
    def shape(self) -> Tuple[int, ...]:
        return self.data.shape

    @property
    def ndim(self) -> int:
        return self.data.ndim

    def dequantize(self, dtype=jnp.float32):
        return self.data.astype(dtype) * self.scale.astype(dtype)[None, :]

    def matmul(self, x, dtype):
        """``x @ W`` under the tensor's mode (see class docstring)."""
        if self.mode == "w8a8":
            return self._matmul_w8a8(x, dtype)
        if self.mode == "pallas":
            return pallas_dequant_matmul(x, self.data, self.scale, dtype)
        return (x @ self.data.astype(dtype)) * self.scale.astype(dtype)

    def _matmul_w8a8(self, x, dtype):
        # dynamic symmetric per-row activation quant: rounding error only
        # (~0.4% rms for typical activations), standard W8A8 serving
        x32 = x.astype(jnp.float32)
        amax = jnp.max(jnp.abs(x32), axis=-1, keepdims=True)
        xs = jnp.where(amax > 0, amax / 127.0, 1.0)
        xq = jnp.clip(jnp.round(x32 / xs), -127, 127).astype(jnp.int8)
        acc = jax.lax.dot_general(
            xq, self.data, (((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        return (acc.astype(jnp.float32) * xs * self.scale).astype(dtype)


def quantize_int8(w: Any, mode: str = "dequant") -> QuantizedTensor:
    """Symmetric per-output-channel int8 quantization of a [in, out] kernel."""
    w = jnp.asarray(w, jnp.float32)
    amax = jnp.max(jnp.abs(w), axis=0)          # [out]
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(w / scale[None, :]), -127, 127).astype(jnp.int8)
    return QuantizedTensor(q, scale, mode=mode)


def quantize_params_int8(params: Any, min_size: int = 65536,
                         mode: str = "dequant", donate: bool = False) -> Any:
    """Swap every large 2-D non-LoRA kernel leaf for a QuantizedTensor.

    LoRA adapters stay fp32 (they are tiny and trained); embeddings stay
    full precision (gather, not matmul); norms/bias are 1-D and skipped.

    ``donate=True`` frees each source kernel's device buffer as soon as
    its int8 twin exists — without it, quantizing a 7B model needs
    bf16 + int8 resident simultaneously (13.5 + 6.8 GB), which does not
    fit a 16 GB chip. The caller's ``params`` tree is INVALID afterwards.
    """
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    treedef = jax.tree_util.tree_structure(params)
    out = []
    for path, leaf in flat:
        # partitioning metadata boxes end the path with GetAttrKey('value');
        # the param NAME is the last dict key
        dict_keys = [str(p.key) for p in path if hasattr(p, "key")]
        name = "/".join(dict_keys)
        is_kernel = dict_keys and dict_keys[-1] in ("kernel", "lm_head")
        if (is_kernel and getattr(leaf, "ndim", 0) == 2
                and leaf.size >= min_size
                and "lora" not in name
                and "embed" not in name):
            q = quantize_int8(leaf, mode=mode)
            if donate and isinstance(leaf, jax.Array):
                jax.block_until_ready(q.data)  # q computed before source dies
                leaf.delete()
            out.append(q)
        else:
            out.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


# -- 4-bit residency (QLoRA-style int4/NF4 base weights) -------------------
#
# Same packed layout as the int4/nf4 wire codec (two codes per uint8,
# per-block f32 absmax scale), so a staged wire payload and the resident
# base are byte-identical formats. Residency uses deterministic
# round-to-nearest — static weights are quantized ONCE, and there is no
# error-feedback loop to absorb stochastic-rounding noise like the wire
# path has, so nearest minimizes per-weight error.

DEFAULT_BLOCK4 = 64  # QLoRA convention for base-weight residency


def _unpack4(packed):
    """[..., k] uint8 → [..., 2k] int32 codes; element 2i is the low
    nibble of byte i (the wire codec's layout)."""
    lo = (packed & 0xF).astype(jnp.int32)
    hi = (packed >> 4).astype(jnp.int32)
    return jnp.stack([lo, hi], axis=-1).reshape(
        packed.shape[:-1] + (2 * packed.shape[-1],))


def _codes_to_vals(codes, fmt: str):
    if fmt == "nf4":
        from fedml_tpu.compression.codecs import NF4_CODEBOOK
        return jnp.asarray(NF4_CODEBOOK)[codes]
    return codes.astype(jnp.float32) - 8.0


def _quantize4_blocks(w, fmt: str, block: int):
    """Flatten → pad to blocks → absmax scale → codes → packed nibbles."""
    flat = jnp.asarray(w, jnp.float32).reshape(-1)
    size = flat.shape[0]
    n_blocks = -(-size // block)
    pad = n_blocks * block - size
    if pad:
        # padding encodes to exact 0 in both formats (int4 code 8,
        # nf4 code 7) — it adds no mass and dequants to zero
        flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)])
    xb = flat.reshape(n_blocks, block)
    amax = jnp.max(jnp.abs(xb), axis=1)
    if fmt == "nf4":
        from fedml_tpu.compression.codecs import _NF4_MIDPOINTS
        scale = jnp.where(amax > 0, amax, 1.0)
        codes = jnp.sum(
            (xb / scale[:, None])[..., None] > jnp.asarray(_NF4_MIDPOINTS),
            axis=-1).astype(jnp.int32)
    else:
        scale = jnp.where(amax > 0, amax / 7.0, 1.0)
        codes = (jnp.clip(jnp.round(xb / scale[:, None]), -7, 7)
                 .astype(jnp.int32) + 8)
    data = (codes[:, 0::2] | (codes[:, 1::2] << 4)).astype(jnp.uint8)
    return data, scale


@jax.tree_util.register_pytree_node_class
class QuantizedTensor4:
    """Blockwise 4-bit weight: ``w ≈ lookup(codes) * scale`` per block.

    ``data`` holds two codes per uint8 (``[n_blocks, block // 2]``),
    ``scale`` one f32 per block — 0.53125 bytes/element at block 64,
    ~0.27× of bf16. ``fmt`` is ``"int4"`` (uniform, codes−8) or ``"nf4"``
    (Dettmers et al. 2023 normal-float codebook; better for the
    zero-centered bell-shaped weight distributions of trained models).

    The dequantized matrix is never resident: :meth:`matmul` inlines the
    unpack → lookup → scale chain when tracing (the fused round / serving
    step fuses it as XLA temporaries), and routes eager calls through the
    cataloged ``quant/dequant_matmul`` program.
    """

    def __init__(self, data, scale, shape, fmt: str = "int4",
                 block: int = DEFAULT_BLOCK4):
        self.data = data              # uint8 [n_blocks, block // 2]
        self.scale = scale            # f32   [n_blocks]
        self.orig_shape = tuple(int(d) for d in shape)
        self.fmt = fmt
        self.block = int(block)

    # -- pytree protocol ------------------------------------------------
    def tree_flatten(self):
        return (self.data, self.scale), (self.orig_shape, self.fmt,
                                         self.block)

    @classmethod
    def tree_unflatten(cls, aux, children):
        shape, fmt, block = aux
        return cls(children[0], children[1], shape, fmt=fmt, block=block)

    # -- array-ish surface ----------------------------------------------
    @property
    def shape(self) -> Tuple[int, ...]:
        return self.orig_shape

    @property
    def ndim(self) -> int:
        return len(self.orig_shape)

    @property
    def size(self) -> int:
        return int(np.prod(self.orig_shape, dtype=np.int64)) \
            if self.orig_shape else 1

    def dequantize(self, dtype=jnp.float32):
        vals = _codes_to_vals(_unpack4(self.data), self.fmt)
        flat = (vals * self.scale.astype(jnp.float32)[:, None]).reshape(-1)
        return flat[:self.size].reshape(self.orig_shape).astype(dtype)

    def matmul(self, x, dtype):
        """``x @ dequant(W)`` with the dequant fused into the consumer."""
        if isinstance(x, jax.core.Tracer):
            # inside an enclosing trace (llm/fused_round, serving
            # prefill/decode): the dequantized tile is an XLA temporary
            # of THAT program — never call a CatalogedProgram on tracers
            return x @ self.dequantize(dtype)
        return _dequant4_matmul_program(
            self.fmt, self.orig_shape, jnp.dtype(dtype).name,
            x, self.data, self.scale)


def _pack4(fmt, block, w):
    return _quantize4_blocks(w, fmt, block)


def _dequant4_matmul(fmt, shape, dtype_name, x, data, scale):
    dt = jnp.dtype(dtype_name)
    vals = _codes_to_vals(_unpack4(data), fmt)
    flat = (vals * scale.astype(jnp.float32)[:, None]).reshape(-1)
    size = int(np.prod(shape, dtype=np.int64)) if shape else 1
    return x @ flat[:size].reshape(shape).astype(dt)


def quantize_int4(w: Any, fmt: str = "int4",
                  block: int = DEFAULT_BLOCK4) -> QuantizedTensor4:
    """Blockwise 4-bit quantization of a kernel (round-to-nearest)."""
    if fmt not in ("int4", "nf4"):
        raise ValueError(
            f"4-bit base format must be 'int4' or 'nf4', got {fmt!r}")
    block = int(block)
    if block < 2 or block > (1 << 20) or block & (block - 1):
        raise ValueError(
            f"4-bit block must be a power of two in [2, 2^20], got {block}")
    shape = tuple(int(d) for d in w.shape)
    data, scale = _pack4_program(fmt, block, jnp.asarray(w, jnp.float32))
    return QuantizedTensor4(data, scale, shape, fmt=fmt, block=block)


def quantize_params_int4(params: Any, fmt: str = "int4",
                         min_size: int = 65536,
                         block: int = DEFAULT_BLOCK4,
                         donate: bool = False) -> Any:
    """Swap every large 2-D non-LoRA kernel leaf for a QuantizedTensor4.

    Same leaf filter and ``donate`` contract as :func:`quantize_params_int8`
    (LoRA/embeddings/1-D stay full precision; ``donate=True`` frees each
    source buffer once its packed twin exists). Records the packed
    footprint in the ``quant/base_bytes`` gauge and bumps
    ``quant/packed_leaves`` so a round trace shows what is 4-bit-resident.
    """
    from fedml_tpu import telemetry

    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    treedef = jax.tree_util.tree_structure(params)
    out: list = []
    packed_bytes = 0
    n_packed = 0
    for path, leaf in flat:
        dict_keys = [str(p.key) for p in path if hasattr(p, "key")]
        name = "/".join(dict_keys)
        is_kernel = dict_keys and dict_keys[-1] in ("kernel", "lm_head")
        if (is_kernel and getattr(leaf, "ndim", 0) == 2
                and leaf.size >= min_size
                and "lora" not in name
                and "embed" not in name):
            q = quantize_int4(leaf, fmt=fmt, block=block)
            if donate and isinstance(leaf, jax.Array):
                jax.block_until_ready(q.data)  # q computed before source dies
                leaf.delete()
            packed_bytes += int(q.data.size) + 4 * int(q.scale.size)
            n_packed += 1
            out.append(q)
        else:
            out.append(leaf)
    reg = telemetry.get_registry()
    reg.gauge("quant/base_bytes").set(packed_bytes)
    if n_packed:
        reg.counter("quant/packed_leaves").inc(n_packed)
    return jax.tree_util.tree_unflatten(treedef, out)


# -- Pallas fused dequant-matmul (the decode-latency path) -----------------
#
# XLA lowers x @ convert(int8) by MATERIALIZING the converted bf16 weights
# (measured: int8 decode 7.1 ms vs bf16 4.5 ms at B8 — PERF_NOTES addendum
# 4), so weight-only int8 bought capacity but lost latency. This kernel
# does what the compiler wouldn't fuse: DMA the weight tile from HBM as
# int8 (half the bytes — decode is weight-bandwidth-bound), convert
# in-VMEM on the VPU, and feed the MXU in bf16. Scales fold into outputs.

# VMEM budget for the weight tile: scoped vmem is 16 MB, and the tile
# shares it with x, the accumulator, and the output block
_TILE_BYTES = 6 * 1024 * 1024


def _pick_tiles(h: int, f: int):
    """(bh, bf) tile of the int8 weight: lane dims multiples of 128 that
    divide the axis, biggest f-block first, tile ≤ _TILE_BYTES."""
    def divisors(dim, cap):
        # 128-lane-aligned blocks only — Mosaic tiling needs them; a dim
        # with no 128-multiple divisor returns [] → caller falls back
        start = min(dim, cap) // 128 * 128
        return [b for b in range(start, 0, -128) if dim % b == 0]

    # narrow f-blocks (≤512) give the DMA/compute pipeline more grid
    # steps to overlap — measured faster than maximal tiles at B=8
    for bf in divisors(f, 512):
        for bh in divisors(h, 8192):
            if bh * bf <= _TILE_BYTES:
                return bh, bf
    return 0, 0


def _dequant_matmul_kernel(x_ref, w_ref, s_ref, o_ref, acc_ref):
    ih = pl.program_id(1)  # reduction step (innermost grid dim)

    @pl.when(ih == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = w_ref[...].astype(jnp.bfloat16)          # int8 → bf16 in VMEM
    acc_ref[...] += jnp.dot(x_ref[...], w,
                            preferred_element_type=jnp.float32)

    @pl.when(ih == pl.num_programs(1) - 1)
    def _done():
        o_ref[...] = (acc_ref[...] * s_ref[...].astype(jnp.float32)
                      ).astype(o_ref.dtype)


def pallas_dequant_matmul(x, q, scale, dtype, interpret=None):
    """``(x @ dequant(q)) * scale`` with the convert fused into the tile
    load. x: [B, H] (or [..., H], flattened), q: int8 [H, F], scale [F].

    ``interpret=None``: compiled on a TPU, the Pallas interpreter
    elsewhere (CPU tests); ``False`` always emits the compiled kernel —
    see :mod:`fedml_tpu.ops.dispatch`. Shapes the kernel was not written
    for (below) take the XLA dequant lowering on every backend."""
    lead = x.shape[:-1]
    h, f = q.shape
    bh, bf = _pick_tiles(h, f)
    rows = int(np.prod(lead)) if lead else 1
    # The kernel exists for the weight-bandwidth-bound DECODE regime
    # (few rows). Prefill (rows ≫ 128) is MXU-bound — the weights
    # amortize over the rows, the x block would blow the VMEM budget
    # (rows × bh bf16), and XLA's dequant costs proportionally little.
    # The kernel's MXU dot runs on bf16 operands, so it is exact only for
    # bf16 compute — fp32 requests take the XLA dequant lowering instead
    # of silently truncating activations (ADVICE r4).
    if (bh == 0 or rows > 128
            or jnp.dtype(dtype) != jnp.dtype(jnp.bfloat16)):
        return (x.reshape(*lead, h) @ q.astype(dtype)) * scale.astype(dtype)
    x2 = x.reshape(-1, h).astype(jnp.bfloat16)
    b = x2.shape[0]
    out = pl.pallas_call(
        _dequant_matmul_kernel,
        grid=(f // bf, h // bh),
        in_specs=[
            pl.BlockSpec((b, bh), lambda j, i: (0, i)),
            pl.BlockSpec((bh, bf), lambda j, i: (i, j)),
            pl.BlockSpec((1, bf), lambda j, i: (0, j)),
        ],
        out_specs=pl.BlockSpec((b, bf), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((b, f), dtype),
        scratch_shapes=[pltpu.VMEM((b, bf), jnp.float32)],
        interpret=kernel_mode(interpret, off_tpu=INTERPRET) == INTERPRET,
    )(x2, q, scale.reshape(1, f))
    return out.reshape(*lead, f)


def matmul_maybe_quantized(x, w, dtype):
    """``x @ w`` that accepts either a plain kernel or a QuantizedTensor —
    the single dispatch point model code uses, so new quantized formats
    only need to be handled here."""
    if isinstance(w, (QuantizedTensor, QuantizedTensor4)):
        return w.matmul(x, dtype)
    return x @ w.astype(dtype)


def tree_bytes(params: Any) -> int:
    """Actual bytes a (possibly quantized) params tree occupies."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(params):
        n = int(np.prod(getattr(leaf, "shape", (0,)) or (0,)))
        total += n * jnp.dtype(getattr(leaf, "dtype", jnp.float32)).itemsize
    return total


# cataloged at module bottom so every helper above exists; imported lazily
# enough that telemetry's own import graph is settled by now
from fedml_tpu.telemetry.profiling import wrap_jit as _wrap_jit  # noqa: E402

_pack4_program = _wrap_jit(
    "quant/pack4", jax.jit(_pack4, static_argnums=(0, 1)),
    static_argnums=(0, 1), multi_shape=True)
_dequant4_matmul_program = _wrap_jit(
    "quant/dequant_matmul",
    jax.jit(_dequant4_matmul, static_argnums=(0, 1, 2)),
    static_argnums=(0, 1, 2), multi_shape=True)
