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
- what a kernel does with a (q block, kv block) pair is decided by where
  the pair sits relative to the causal diagonal (:func:`causal_plan`, the
  one schedule all three kernels are built from). *Above* it the pair is
  neither computed nor fetched: the moving index of the ``BlockSpec`` is
  clamped to the last block the row of the grid needs, consecutive steps
  name the same block and the pipeline issues no copy. *Below* it the
  body carries no iota, compare or select. *On* it the block is walked in
  row strips of ``_SUB`` rows: each strip multiplies only the columns its
  last row sees, masks only the ``_SUB``-wide piece the diagonal crosses,
  and updates the softmax statistics once. A block that holds a ragged
  edge (``T % block_q``, ``S % block_k``) is masked whole, by position;
- a strip's area does not depend on the block it is cut from, so the grid's
  blocks are as large as pays (:func:`_block`): fewer steps, fewer re-fetches
  of K/V and of the q-side blocks, fewer statistics updates;
- a model calls the kernels once a layer with one signature, so ``_fwd`` and
  ``_bwd`` are ``jit``s: traced once and lowered once a program;
- a value head may be narrower than the score lanes (latent attention:
  192 score lanes, 128 value lanes): q and k are ``[.., dk]``, v, the
  output and its cotangent ``[.., dv]``, and each block, accumulator and
  gradient is as wide as what it holds. Where ``dk == dv`` every spec and
  scratch is what one head size gave;
- off-TPU (CPU tests) the same kernels run under ``interpret=True``;
  which form a call gets is decided in ``ops/dispatch.py``.

The public entry is :func:`flash_attention` — identical math to
``jax.nn.dot_product_attention`` for supported shapes, verified by tests.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from fedml_tpu.ops.dispatch import INTERPRET, REFERENCE, kernel_mode
from fedml_tpu.telemetry.spans import get_tracer

DEFAULT_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)

# Edge of a sub-tile of a block on the diagonal: one MXU tile, one register
# of lanes. Blocks that are not whole multiples of it are not sub-tiled.
_SUB = 128
# Rows of a strip of a block below the diagonal (no diagonal to hug there).
_ROWS = 512
# Largest grid block along T or S at head sizes up to 128.
_BLOCK_CAP = 2048
# The kernels' own VMEM ceiling (the chip holds 128 MiB; the default scoped
# limit of 16 MiB is what the 512 x 1024 blocks were sized to).
_VMEM_LIMIT = 64 * 1024 * 1024

SKIPPED, UNMASKED, MASKED = "skipped", "unmasked", "masked"
_PLAN_COUNTS = ("visited", "masked", "unmasked", "skipped", "area_ratio")


# ---------------------------------------------------------------------------
# the causal schedule
# ---------------------------------------------------------------------------
class _Geometry(NamedTuple):
    """What the three kernels share: the call's static shape facts."""
    causal: bool
    block_q: int
    block_k: int
    q_steps: int
    kv_steps: int
    t_len: int
    s_len: int
    phases: tuple  # ((rel, strips), ...) of the blocks the diagonal crosses


def _above(rel, block_q):
    """Every column of the block lies past every row (``rel`` = first row
    minus first column; Python ints at trace time, scalars in a kernel)."""
    return rel <= -block_q


def _below(rel, block_k):
    """Every column of the block lies at or before every row."""
    return rel >= block_k - 1


def _last_kv(g: _Geometry, qi):
    """The last kv block q block ``qi`` needs (fwd, dq clamp here)."""
    if not g.causal:
        return g.kv_steps - 1
    return jnp.minimum((qi * g.block_q + g.block_q - 1) // g.block_k,
                       g.kv_steps - 1)


def _first_q(g: _Geometry, ki):
    """The first q block kv block ``ki`` is seen by (dkv clamps here)."""
    return (ki * g.block_k) // g.block_q if g.causal else 0


def _block(n: int, d: int, unaligned: int) -> int:
    """The grid block along a sequence of ``n`` positions at head size ``d``.

    Since a block on the diagonal is walked in strips, a larger block costs
    no score area; it saves grid steps (about half a microsecond each on
    the v5e), re-fetches of K/V and of the q-side blocks, and statistics
    updates. 2048 is where the v5e stopped gaining (kernels alone at
    T4096: 512 x 1024 5.31 ms, 1024 x 2048 4.50, 2048 x 2048 4.40;
    4096 x 4096 4.34 for 22 s more of Mosaic compile; PERF.md, PR 28).
    Past a head of 128 the cap shrinks with the head, so a block's bytes
    stay what they were: at D256 the blocks are 1024 x 1024, which compile
    under ``_VMEM_LIMIT`` and read, at ``[1, 20, 4096, 256]`` on the v5e,
    fwd 1.400, dq 1.748, dkv 2.250 ms a call = 72.7 % of the kernels' own
    9 products at the bf16 peak (the same call at ``[1, 32, 4096, 64]``
    1.309 / 1.333 / 1.808 ms = 35.3 %, at 32 / 4 heads of 128 1.317 /
    1.322 / 1.818 ms = 70.4 %; PERF.md, PR 37).
    A head that is no power of two (192 score lanes) gets the cap of the
    next one up, so a block still divides a sequence of whole 1024s.
    A sequence that is no multiple of ``_SUB`` keeps the former blocks.
    """
    if n % _SUB != 0:
        return unaligned
    cap = _BLOCK_CAP * 128 // max(d, 128)
    cap = 1 << (cap.bit_length() - 1)
    if n <= cap:
        return n
    return next((b for b in (cap, cap // 2, cap // 4) if n % b == 0), cap)


def _strips(rel: int, block_q: int, block_k: int):
    """Row strips of a block the diagonal crosses: ``(r0, r1, segments)``,
    ``segments`` up to two ``(c0, c1, masked)`` column ranges — the columns
    every row of the strip sees, then those only its later rows see. Row
    ``r`` of the block sees column ``c`` when ``c <= r + rel``."""
    sub = block_q % _SUB == 0 and block_k % _SUB == 0
    sq, sk = (_SUB, _SUB) if sub else (block_q, block_k)
    strips = []
    for r0 in range(0, block_q, sq):
        r1 = min(r0 + sq, block_q)
        clear = min(max(r0 + rel + 1, 0), block_k) // sk * sk
        need = min(-(-min(max(r1 + rel, 0), block_k) // sk) * sk, block_k)
        segments = ((0, clear, False),) if clear else ()
        if need > clear:
            segments += ((clear, need, True),)
        strips.append((r0, r1, segments))
    return tuple(strips)


def _schedule(t: int, s: int, block_q: int, block_k: int, causal: bool):
    """``(geometry, pairs, tiles)``: ``pairs`` is the kind of every grid
    pair in grid order, ``tiles`` every rectangle ``(r0, r1, c0, c1, kind)``
    of the ``[T, S]`` score square, each position in exactly one."""
    if causal and t != s:
        raise ValueError(
            f"causal flash attention needs T == S (got T={t}, S={s}): the "
            "kernels put the diagonal top-left, reference_attention "
            "bottom-right")
    bq, bk = min(block_q, t), min(block_k, s)
    q_steps, kv_steps = pl.cdiv(t, bq), pl.cdiv(s, bk)
    pairs, tiles, phases = [], [], {}
    for qi in range(q_steps):
        for ki in range(kv_steps):
            q0, k0 = qi * bq, ki * bk
            rel = q0 - k0
            edge = ((qi == q_steps - 1 and t % bq != 0)
                    or (ki == kv_steps - 1 and s % bk != 0))
            crossed = False
            if causal and _above(rel, bq):
                kind = SKIPPED
            elif edge:
                kind = MASKED
            elif not causal or _below(rel, bk):
                kind = UNMASKED
            else:  # the diagonal crosses it: strips of tiles of every kind
                kind, crossed = MASKED, True
            pairs.append(kind)
            if not crossed:
                tiles.append((q0, q0 + bq, k0, k0 + bk, kind))
                continue
            for r0, r1, segments in phases.setdefault(
                    rel, _strips(rel, bq, bk)):
                end = 0
                for c0, c1, masked in segments:
                    tiles.append((q0 + r0, q0 + r1, k0 + c0, k0 + c1,
                                  MASKED if masked else UNMASKED))
                    end = c1
                if end < bk:
                    tiles.append((q0 + r0, q0 + r1, k0 + end, k0 + bk,
                                  SKIPPED))
    geometry = _Geometry(causal, bq, bk, q_steps, kv_steps, t, s,
                         tuple(sorted(phases.items())))
    return geometry, pairs, tiles


def causal_plan(t: int, s: int, block_q: int, block_k: int,
                causal: bool) -> dict:
    """What the kernels do with the ``[T, S]`` score square, by position.

    Counts of grid pairs — ``visited`` (fetched and computed) = ``masked``
    (the diagonal crosses them, or they hold a ragged edge) + ``unmasked``;
    ``skipped`` (neither fetched nor computed) — and ``area_ratio``, the
    scores computed over the scores the call needs. ``pairs`` (each grid
    pair's kind, in grid order) and ``tiles`` (every rectangle ``(r0, r1,
    c0, c1, kind)`` of the square once) are the schedule itself, and
    ``geometry`` is what the ``pallas_call`` builders take from it.
    """
    g, pairs, tiles = _schedule(t, s, block_q, block_k, causal)
    computed = sum((r1 - r0) * (c1 - c0)
                   for r0, r1, c0, c1, kind in tiles if kind != SKIPPED)
    return {
        "visited": len(pairs) - pairs.count(SKIPPED),
        "masked": pairs.count(MASKED),
        "unmasked": pairs.count(UNMASKED),
        "skipped": pairs.count(SKIPPED),
        "area_ratio": computed / (t * (t + 1) // 2 if causal else t * s),
        "block_q": g.block_q,
        "block_k": g.block_k,
        "pairs": tuple(pairs),
        "tiles": tuple(tiles),
        "geometry": g,
    }


def _geometry(kernels, q, k, v, block_q, block_k, causal) -> _Geometry:
    """The call's schedule, and one ``kernel/flash/plan`` event for each of
    ``kernels`` (this runs when a kernel is traced, never in a round)."""
    (_, h, t, d), (_, hkv, s, _) = q.shape, k.shape
    plan = causal_plan(t, s, block_q, block_k, causal)
    for kernel in kernels:
        get_tracer().event(
            "kernel/flash/plan", kernel=kernel, t=t, s=s, d=d,
            v_dim=v.shape[-1], heads=h, kv_heads=hkv,
            **{name: plan[name] for name in _PLAN_COUNTS})
    return plan["geometry"]


# ---------------------------------------------------------------------------
# what the three kernels share
# ---------------------------------------------------------------------------
def _all(*preds):
    """Conjunction of Python bools (decided at trace time) and scalars."""
    out = True
    for pred in preds:
        if pred is False:
            return False
        if pred is not True:
            out = pred if out is True else jnp.logical_and(out, pred)
    return out


def _any(*preds):
    """Disjunction of Python bools and scalars (``False and x`` is False)."""
    out = False
    for pred in preds:
        if pred is True:
            return True
        if pred is not False:
            out = pred if out is False else jnp.logical_or(out, pred)
    return out


def _when(pred, body):
    if pred is True:
        body()
    elif pred is not False:
        pl.when(pred)(body)


def _masked(s, row_off, col_off, causal, col_limit=None):
    """``s`` with the positions the call must not see at the mask value.
    The offsets place ``s[0, 0]``: static and relative to the block for a
    piece the diagonal crosses, traced and absolute for a ragged edge."""
    rows = row_off + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = col_off + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = rows >= cols if causal else None
    if col_limit is not None:  # phantom padding columns past S
        inside = cols < col_limit
        valid = inside if valid is None else valid & inside
    return jnp.where(valid, s, DEFAULT_MASK_VALUE)


def _walk(g: _Geometry, qi, ki, tile):
    """Run ``tile(r0, rows, segments, mask, edge)`` for each row strip block
    ``(qi, ki)`` needs. ``mask(s, r0, c0)`` masks a score piece whose
    segment says so; ``edge`` is ``None`` or the block's ``(first row,
    first column)`` when padding rows or columns have to be zeroed."""
    q_start, k_start = qi * g.block_q, ki * g.block_k
    rel = q_start - k_start

    def rolled(masked, mask, edge):
        # equal strips of the whole block: one traced body, rolled
        rows = _ROWS if g.block_q % _ROWS == 0 else g.block_q
        whole = ((0, g.block_k, masked),)

        def strip(i, carry):
            tile(pl.multiple_of(i * rows, rows), rows, whole, mask, edge)
            return carry

        def body():
            if rows == g.block_q:
                tile(0, rows, whole, mask, edge)
            else:
                jax.lax.fori_loop(0, g.block_q // rows, strip, 0)
        return body

    def crossed(phase, strips):
        def body():
            for r0, r1, segments in strips:
                if segments:
                    tile(r0, r1 - r0, segments,
                         lambda s, r0, c0: _masked(s, r0 + phase, c0, True),
                         None)
        return body

    def ragged_mask(s, r0, c0):
        return _masked(s, q_start + r0, k_start + c0, g.causal, g.s_len)

    edge = _any(g.t_len % g.block_q != 0 and qi == g.q_steps - 1,
                g.s_len % g.block_k != 0 and ki == g.kv_steps - 1)
    inner = True if edge is False else jnp.logical_not(edge)
    _when(_all(inner, _below(rel, g.block_k) if g.causal else True),
          rolled(False, None, None))
    for phase, strips in g.phases:
        _when(_all(inner, rel == phase), crossed(phase, strips))
    _when(_all(edge, jnp.logical_not(_above(rel, g.block_q))
               if g.causal else True),
          rolled(True, ragged_mask, (q_start, k_start)))


def _zero_phantom_rows(x, start, limit):
    """Zero block-padding rows past ``limit`` — padded loads can be NaN/garbage,
    and 0*NaN from an otherwise-masked contribution would still poison sums."""
    rows = start + jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    return jnp.where(rows < limit, x, 0.0)


def _rows_of(g: _Geometry, edge, r0, rows, *refs):
    """``rows`` rows from ``r0`` of q-side blocks in float32, padding rows
    zeroed."""
    out = [ref[0, 0, pl.ds(r0, rows)].astype(jnp.float32) for ref in refs]
    if edge is not None and g.t_len % g.block_q != 0:
        out = [_zero_phantom_rows(x, edge[0] + r0, g.t_len) for x in out]
    return out


def _cols_of(g: _Geometry, edge, c0, c1, *refs):
    """Rows ``[c0, c1)`` of kv-side blocks (columns of the scores)."""
    out = [ref[0, 0, c0:c1].astype(jnp.float32) for ref in refs]
    if edge is not None and g.s_len % g.block_k != 0:
        out = [_zero_phantom_rows(x, edge[1] + c0, g.s_len) for x in out]
    return out


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32)


_NT = ((1,), (1,))  # a @ b.T
_NN = ((1,), (0,))  # a @ b
_TN = ((0,), (0,))  # a.T @ b


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_i, l_i, *,
                sm_scale: float, g: _Geometry):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_i[...] = jnp.full_like(m_i, -jnp.inf)
        l_i[...] = jnp.zeros_like(l_i)

    def tile(r0, rows, segments, mask, edge):
        (q,) = _rows_of(g, edge, r0, rows, q_ref)  # [rows, d]
        scores = []
        for c0, c1, masked in segments:
            (k,) = _cols_of(g, edge, c0, c1, k_ref)  # [cols, d]
            s = _dot(q, k, _NT) * sm_scale
            scores.append(mask(s, r0, c0) if masked else s)
        # the statistics stay [rows, lanes]: a [rows] vector would be laid
        # along lanes and every use of it a relayout
        rs = pl.ds(r0, rows)
        m_prev = m_i[rs]
        m_cur = m_prev
        for s in scores:
            m_cur = jnp.maximum(m_cur, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        l_cur = alpha * l_i[rs]
        pv = None
        for (c0, c1, _), s in zip(segments, scores):
            (v,) = _cols_of(g, edge, c0, c1, v_ref)
            p = jnp.exp(s - m_cur[:, :1])
            l_cur = l_cur + jnp.sum(p, axis=1, keepdims=True)
            part = _dot(p, v, _NN)
            pv = part if pv is None else pv + part
        acc[rs] = acc[rs] * alpha[:, :1] + pv
        m_i[rs] = m_cur
        l_i[rs] = l_cur

    _walk(g, qi, ki, tile)

    @pl.when(ki == g.kv_steps - 1)
    def _finalize():
        l = jnp.maximum(l_i[:, :1], 1e-30)
        o_ref[0, 0] = (acc[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = m_i[:, :1] + jnp.log(l)


def _specs(g: _Geometry, group: int, d: int, dv: int):
    """BlockSpecs of a ``(b, h, q_steps, kv_steps)`` grid (fwd, dq): q-side
    and kv-side blocks at the score lanes ``d`` and at the value lanes
    ``dv``, and the row statistic; the kv index stops at the last block the
    q block needs."""
    def q_side(width):
        return pl.BlockSpec((1, 1, g.block_q, width),
                            lambda bi, hi, qi, ki: (bi, hi, qi, 0))

    def kv_side(width):
        return pl.BlockSpec(
            (1, 1, g.block_k, width),
            lambda bi, hi, qi, ki: (bi, hi // group,
                                    jnp.minimum(ki, _last_kv(g, qi)), 0))

    return q_side(d), kv_side(d), q_side(dv), kv_side(dv), q_side(1)


# A model calls the kernels once a layer with one signature: under ``jit``
# the body is traced once and lowered once (one function of the module,
# called from every layer), not once a layer.
@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7))
def _fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret):
    b, h, t, _ = q.shape
    _, hkv, s, dv = v.shape
    g = _geometry(("flash_fwd",), q, k, v, block_q, block_k, causal)
    q_spec, k_spec, o_spec, v_spec, lse_spec = _specs(
        g, h // hkv, q.shape[-1], dv)

    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, sm_scale=sm_scale, g=g),
        grid=(b, h, g.q_steps, g.kv_steps),
        in_specs=[q_spec, k_spec, v_spec],
        out_specs=[o_spec, lse_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, t, dv), q.dtype),
            jax.ShapeDtypeStruct((b, h, t, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((g.block_q, dv), jnp.float32),
            pltpu.VMEM((g.block_q, 128), jnp.float32),
            pltpu.VMEM((g.block_q, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------
def _bwd_tile(g: _Geometry, refs, sm_scale, r0, rows, segment, mask, edge):
    """``(p, ds, q, do, k)`` of one score piece, shared by dq and dkv."""
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = refs
    c0, c1, masked = segment
    q, do = _rows_of(g, edge, r0, rows, q_ref, do_ref)
    k, v = _cols_of(g, edge, c0, c1, k_ref, v_ref)
    # [rows, 1], as stored: no [rows] vector (see the forward)
    lse, delta = _rows_of(g, edge, r0, rows, lse_ref, delta_ref)
    s = _dot(q, k, _NT) * sm_scale
    if masked:
        s = mask(s, r0, c0)
    p = jnp.exp(s - lse)
    ds = p * (_dot(do, v, _NT) - delta) * sm_scale
    if edge is not None and g.t_len % g.block_q != 0:
        # phantom q rows (block padding past T): their lse / delta were
        # zeroed above, but exp(s) and 0 * inf must not reach the dk/dv sums
        # (dq drops those rows when the block is written)
        p = _zero_phantom_rows(p, edge[0] + r0, g.t_len)
        ds = _zero_phantom_rows(ds, edge[0] + r0, g.t_len)
    return p, ds, q, do, k


def _bwd_dq_kernel(*refs, sm_scale, g: _Geometry):
    *in_refs, dq_ref, dq_acc = refs
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def tile(r0, rows, segments, mask, edge):
        for segment in segments:
            _, ds, _, _, k = _bwd_tile(g, in_refs, sm_scale, r0, rows,
                                       segment, mask, edge)
            dq_acc[pl.ds(r0, rows)] += _dot(ds, k, _NN)

    _walk(g, qi, ki, tile)

    @pl.when(ki == g.kv_steps - 1)
    def _write():
        dq_ref[0, 0] = dq_acc[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, sm_scale, g: _Geometry):
    *in_refs, dk_ref, dv_ref, dk_acc, dv_acc = refs
    ki = pl.program_id(2)
    qi = pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def tile(r0, rows, segments, mask, edge):
        for segment in segments:
            c0, c1, _ = segment
            p, ds, q, do, _ = _bwd_tile(g, in_refs, sm_scale, r0, rows,
                                        segment, mask, edge)
            dv_acc[c0:c1] += _dot(p, do, _TN)
            dk_acc[c0:c1] += _dot(ds, q, _TN)

    _walk(g, qi, ki, tile)

    @pl.when(qi == g.q_steps - 1)
    def _write():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _bwd(sm_scale, causal, block_q, block_k, interpret, res, do):
    q, k, v, out, lse = res
    b, h, t, d = q.shape
    _, hkv, s, dv = v.shape
    group = h // hkv
    g = _geometry(("flash_bwd_dq", "flash_bwd_dkv"), q, k, v, block_q,
                  block_k, causal)
    bq, bk = g.block_q, g.block_k
    delta = jnp.sum(
        do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1, keepdims=True
    )  # [b, h, t, 1] — trailing singleton keeps TPU block tiling legal

    def scratch(shape):
        return pltpu.VMEM(shape, jnp.float32)

    q_spec, k_spec, do_spec, v_spec, lse_spec = _specs(g, group, d, dv)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, sm_scale=sm_scale, g=g),
        grid=(b, h, g.q_steps, g.kv_steps),
        in_specs=[q_spec, k_spec, v_spec, do_spec, lse_spec, lse_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[scratch((bq, d))],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="flash_bwd_dq",
    )(q, k, v, do, lse, delta)

    # dk/dv accumulate over q heads within a group as well: run per q-head
    # into a [b, h, ...] buffer, then sum the group axis outside the kernel.
    # The q index starts at the first block that sees the kv block.
    def q_map(bi, hi, ki, qi):
        return (bi, hi, jnp.maximum(qi, _first_q(g, ki)), 0)

    def kv_map(bi, hi, ki, qi):
        return (bi, hi // group, ki, 0)

    def out_map(bi, hi, ki, qi):
        return (bi, hi, ki, 0)

    kq_spec = pl.BlockSpec((1, 1, bq, d), q_map)
    kdo_spec = pl.BlockSpec((1, 1, bq, dv), q_map)
    kk_spec = pl.BlockSpec((1, 1, bk, d), kv_map)
    kv_spec = pl.BlockSpec((1, 1, bk, dv), kv_map)
    klse_spec = pl.BlockSpec((1, 1, bq, 1), q_map)
    dk_spec = pl.BlockSpec((1, 1, bk, d), out_map)
    dv_spec = pl.BlockSpec((1, 1, bk, dv), out_map)
    dk_h, dv_h = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, sm_scale=sm_scale, g=g),
        grid=(b, h, g.kv_steps, g.q_steps),
        in_specs=[kq_spec, kk_spec, kv_spec, kdo_spec, klse_spec, klse_spec],
        out_specs=[dk_spec, dv_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, d), jnp.float32),
            jax.ShapeDtypeStruct((b, h, s, dv), jnp.float32),
        ],
        scratch_shapes=[scratch((bk, d)), scratch((bk, dv))],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q, k, v, do, lse, delta)
    dk = dk_h.reshape(b, hkv, group, s, d).sum(axis=2).astype(k.dtype)
    return dq, dk, dv_h.reshape(b, hkv, group, s, dv).sum(axis=2).astype(
        v.dtype)


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
    """Plain-XLA attention (numerics oracle + CPU fallback). q, k
    ``[B, H(kv), T, D]``, v ``[B, Hkv, S, Dv]``; the output is ``[B, H, T,
    Dv]``."""
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
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Tiled online-softmax attention. q: [B,H,T,D]; k: [B,Hkv,S,D];
    v: [B,Hkv,S,Dv] (``Dv`` may be narrower than ``D``); out: [B,H,T,Dv].

    ``block_q`` / ``block_k`` are the grid's blocks (clipped to T / S); a
    call that names none gets :func:`_block`'s, sized on the chip by the
    wider of the two head sizes.

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
    d = max(q.shape[-1], v.shape[-1])
    return _flash(q, k, v, sm_scale, causal,
                  block_q or _block(q.shape[2], d, 512),
                  block_k or _block(k.shape[2], d, 1024),
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
