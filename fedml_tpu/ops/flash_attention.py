"""Pallas TPU flash attention — the long-context hot op.

Parity target: the reference's long-context support is a FlashAttention
monkey-patch over HF models (``train/llm/models/attention.py:30-101``,
GPT-NeoX impl ``models/modeling_gpt_neox.py``). Here the kernel is a
first-class framework op: an online-softmax tiled attention written in
Pallas for the TPU MXU/VMEM hierarchy, with a custom VJP whose backward is
also two Pallas kernels (dq; dk/dv) so neither pass materialises the
[T, S] score matrix in HBM.

Design notes (pallas_guide.md):
- grid is (batch, q_heads, q_blocks, kv_blocks) with the kv axis innermost —
  on TPU the innermost grid axis is sequential per core, so the online
  softmax accumulators live in VMEM scratch across kv steps and the output
  block is written once, on the last kv step;
- GQA is expressed in the BlockSpec index maps (kv head = q head // group)
  instead of materialising repeated K/V in HBM;
- causal masking skips whole kv blocks past the diagonal via ``pl.when``
  (compute is masked, the DMA pipeline stays regular);
- off-TPU (CPU tests) the same kernels run under ``interpret=True``;
  which form a call gets is decided in ``ops/dispatch.py``.

The public entry is :func:`flash_attention` — identical math to
``jax.nn.dot_product_attention`` for supported shapes, verified by tests.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from fedml_tpu.ops.dispatch import INTERPRET, REFERENCE, kernel_mode

DEFAULT_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------

def _zero_phantom_rows(x, start, limit):
    """Zero block-padding rows past ``limit`` — padded loads can be NaN/garbage,
    and 0*NaN from an otherwise-masked contribution would still poison sums."""
    rows = start + jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    return jnp.where(rows < limit, x, 0.0)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_i, l_i, *,
                sm_scale: float, causal: bool, block_q: int, block_k: int,
                kv_steps: int, s_len: int, t_len: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_i[...] = jnp.full_like(m_i, -jnp.inf)
        l_i[...] = jnp.zeros_like(l_i)

    q_start = qi * block_q
    k_start = ki * block_k

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # [block_q, d]
        k = k_ref[0, 0].astype(jnp.float32)  # [block_k, d]
        v = v_ref[0, 0].astype(jnp.float32)  # [block_k, d]
        if (t_len % block_q) != 0:
            q = _zero_phantom_rows(q, q_start, t_len)
        if (s_len % block_k) != 0:
            k = _zero_phantom_rows(k, k_start, s_len)
            v = _zero_phantom_rows(v, k_start, s_len)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale  # [block_q, block_k]
        if causal or (s_len % block_k) != 0:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            valid = cols < s_len  # phantom padding columns past S
            if causal:
                valid = valid & (rows >= cols)
            s = jnp.where(valid, s, DEFAULT_MASK_VALUE)
        m_prev = m_i[:, 0]
        l_prev = l_i[:, 0]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        l_cur = alpha * l_prev + jnp.sum(p, axis=1)
        acc[...] = acc[...] * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_i[...] = jnp.broadcast_to(m_cur[:, None], m_i.shape)
        l_i[...] = jnp.broadcast_to(l_cur[:, None], l_i.shape)

    if causal:
        # whole kv block strictly above the diagonal contributes nothing
        pl.when(k_start <= q_start + block_q - 1)(_compute)
    else:
        _compute()

    @pl.when(ki == kv_steps - 1)
    def _finalize():
        l = jnp.maximum(l_i[:, 0], 1e-30)
        o_ref[0, 0] = (acc[...] / l[:, None]).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_i[:, :1] + jnp.log(l)[:, None])


def _fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret):
    b, h, t, d = q.shape
    _, hkv, s, _ = k.shape
    group = h // hkv
    block_q = min(block_q, t)
    block_k = min(block_k, s)
    q_steps, kv_steps = pl.cdiv(t, block_q), pl.cdiv(s, block_k)

    grid = (b, h, q_steps, kv_steps)
    q_spec = pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0))
    kv_spec = pl.BlockSpec(
        (1, 1, block_k, d), lambda bi, hi, qi, ki: (bi, hi // group, ki, 0)
    )
    out_spec = pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0))
    lse_spec = pl.BlockSpec(
        (1, 1, block_q, 1), lambda bi, hi, qi, ki: (bi, hi, qi, 0)
    )

    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, kv_steps=kv_steps, s_len=s, t_len=t,
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[out_spec, lse_spec],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((b, h, t, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------
def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_acc, *, sm_scale, causal, block_q, block_k, kv_steps,
                   s_len, t_len):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    q_start, k_start = qi * block_q, ki * block_k

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0, :, 0]
        delta = delta_ref[0, 0, :, 0]
        if (t_len % block_q) != 0:
            q = _zero_phantom_rows(q, q_start, t_len)
            do = _zero_phantom_rows(do, q_start, t_len)
            lse = jnp.where(q_start + jnp.arange(block_q) < t_len, lse, 0.0)
            delta = jnp.where(q_start + jnp.arange(block_q) < t_len, delta, 0.0)
        if (s_len % block_k) != 0:
            k = _zero_phantom_rows(k, k_start, s_len)
            v = _zero_phantom_rows(v, k_start, s_len)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale
        if causal or (s_len % block_k) != 0:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            valid = cols < s_len
            if causal:
                valid = valid & (rows >= cols)
            s = jnp.where(valid, s, DEFAULT_MASK_VALUE)
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta[:, None]) * sm_scale
        dq_acc[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    if causal:
        pl.when(k_start <= q_start + block_q - 1)(_compute)
    else:
        _compute()

    @pl.when(ki == kv_steps - 1)
    def _write():
        dq_ref[0, 0] = dq_acc[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, sm_scale, causal,
                    block_q, block_k, q_steps, t_len, s_len):
    ki = pl.program_id(2)
    qi = pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q_start, k_start = qi * block_q, ki * block_k

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0, :, 0]
        delta = delta_ref[0, 0, :, 0]
        if (t_len % block_q) != 0:
            q = _zero_phantom_rows(q, q_start, t_len)
            do = _zero_phantom_rows(do, q_start, t_len)
            lse = jnp.where(q_start + jnp.arange(block_q) < t_len, lse, 0.0)
            delta = jnp.where(q_start + jnp.arange(block_q) < t_len, delta, 0.0)
        if (s_len % block_k) != 0:
            k = _zero_phantom_rows(k, k_start, s_len)
            v = _zero_phantom_rows(v, k_start, s_len)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale
        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        if causal:
            s = jnp.where(rows >= cols, s, DEFAULT_MASK_VALUE)
        p = jnp.exp(s - lse[:, None])  # [bq, bk]
        if (t_len % block_q) != 0:
            # phantom q rows (block padding past T) carry garbage lse/delta —
            # zero their probability mass so dk/dv sums stay exact
            p = jnp.where(rows < t_len, p, 0.0)
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta[:, None]) * sm_scale
        if (t_len % block_q) != 0:
            # delta for phantom rows is garbage; p==0 there, but 0*inf=nan
            ds = jnp.where(rows < t_len, ds, 0.0)
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    if causal:
        # q block entirely above diagonal sees none of this kv block
        pl.when(q_start + block_q - 1 >= k_start)(_compute)
    else:
        _compute()

    @pl.when(qi == q_steps - 1)
    def _write():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd(sm_scale, causal, block_q, block_k, interpret, res, do):
    q, k, v, out, lse = res
    b, h, t, d = q.shape
    _, hkv, s, _ = k.shape
    group = h // hkv
    bq, bk = min(block_q, t), min(block_k, s)
    q_steps, kv_steps = pl.cdiv(t, bq), pl.cdiv(s, bk)
    delta = jnp.sum(
        do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1, keepdims=True
    )  # [b, h, t, 1] — trailing singleton keeps TPU block tiling legal

    def scratch(shape):
        return pltpu.VMEM(shape, jnp.float32)

    q_spec = pl.BlockSpec((1, 1, bq, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0))
    kv_spec = pl.BlockSpec((1, 1, bk, d), lambda bi, hi, qi, ki: (bi, hi // group, ki, 0))
    lse_spec = pl.BlockSpec((1, 1, bq, 1), lambda bi, hi, qi, ki: (bi, hi, qi, 0))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=bq, block_k=bk, kv_steps=kv_steps,
                          s_len=s, t_len=t),
        grid=(b, h, q_steps, kv_steps),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, lse_spec, lse_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[scratch((bq, d))],
        interpret=interpret,
        name="flash_bwd_dq",
    )(q, k, v, do, lse, delta)

    # dk/dv accumulate over q heads within a group as well: run per q-head
    # into a [b, h, ...] buffer, then sum the group axis outside the kernel.
    kq_spec = pl.BlockSpec((1, 1, bq, d), lambda bi, hi, ki, qi: (bi, hi, qi, 0))
    kkv_spec = pl.BlockSpec((1, 1, bk, d), lambda bi, hi, ki, qi: (bi, hi // group, ki, 0))
    klse_spec = pl.BlockSpec((1, 1, bq, 1), lambda bi, hi, ki, qi: (bi, hi, qi, 0))
    kout_spec = pl.BlockSpec((1, 1, bk, d), lambda bi, hi, ki, qi: (bi, hi, ki, 0))
    dk_h, dv_h = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=bq, block_k=bk, q_steps=q_steps,
                          t_len=t, s_len=s),
        grid=(b, h, kv_steps, q_steps),
        in_specs=[kq_spec, kkv_spec, kkv_spec, kq_spec, klse_spec, klse_spec],
        out_specs=[kout_spec, kout_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, d), jnp.float32),
            jax.ShapeDtypeStruct((b, h, s, d), jnp.float32),
        ],
        scratch_shapes=[scratch((bk, d)), scratch((bk, d))],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q, k, v, do, lse, delta)
    dk = dk_h.reshape(b, hkv, group, s, d).sum(axis=2).astype(k.dtype)
    dv = dv_h.reshape(b, hkv, group, s, d).sum(axis=2).astype(v.dtype)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------
@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7)
)
def _flash(q, k, v, sm_scale, causal, block_q, block_k, interpret):
    out, _ = _fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret)
    return out


def _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret):
    out, lse = _fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd(sm_scale, causal, block_q, block_k, interpret, res, do):
    return _bwd(sm_scale, causal, block_q, block_k, interpret, res, do)


_flash.defvjp(_flash_fwd, _flash_bwd)


def reference_attention(q, k, v, causal: bool = True,
                        sm_scale: Optional[float] = None):
    """Plain-XLA attention (numerics oracle + CPU fallback). [B,H,T,D] layout."""
    b, h, t, d = q.shape
    _, hkv, s_len, _ = k.shape
    if hkv != h:
        k = jnp.repeat(k, h // hkv, axis=1)
        v = jnp.repeat(v, h // hkv, axis=1)
    scale = sm_scale if sm_scale is not None else d ** -0.5
    logits = jnp.einsum(
        "bhtd,bhsd->bhts", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    if causal:
        mask = jnp.tril(jnp.ones((t, s_len), bool), k=s_len - t)
        logits = jnp.where(mask, logits, DEFAULT_MASK_VALUE)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhts,bhsd->bhtd", probs, v.astype(jnp.float32)).astype(q.dtype)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 1024,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Tiled online-softmax attention. q: [B,H,T,D]; k/v: [B,Hkv,S,D].

    ``interpret=None`` (model code): the compiled kernels on a TPU, the
    plain-XLA reference elsewhere. ``interpret=True`` runs the kernels
    under the Pallas interpreter (CPU unit tests; refused on a TPU);
    ``interpret=False`` always emits the compiled kernels — see
    :mod:`fedml_tpu.ops.dispatch`.
    """
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    mode = kernel_mode(interpret, off_tpu=REFERENCE)
    if mode == REFERENCE:
        return reference_attention(q, k, v, causal=causal, sm_scale=sm_scale)
    return _flash(q, k, v, sm_scale, causal, block_q, block_k,
                  mode == INTERPRET)


def make_sharded_flash_attention(mesh, batch_axes, head_axis,
                                 causal: bool = True):
    """:func:`flash_attention` as an ``attention_fn(q, k, v)`` for a
    program GSPMD partitions over ``mesh``.

    The SPMD partitioner refuses a bare Mosaic call ("Mosaic kernels
    cannot be automatically partitioned"), so the call sits in a
    ``shard_map``: each device runs the kernel on its own batch rows
    (``batch_axes``) and heads (``head_axis``). Attention mixes neither,
    so there is no communication and q/k/v are never gathered. On a
    one-device mesh the map is the identity.
    """
    from jax.sharding import PartitionSpec as P

    spec = P(batch_axes, head_axis, None, None)
    return jax.shard_map(
        functools.partial(flash_attention, causal=causal), mesh=mesh,
        in_specs=(spec, spec, spec), out_specs=spec, check_vma=False)
