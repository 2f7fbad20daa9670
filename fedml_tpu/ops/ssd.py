"""The selective state-space recurrence of a Mamba-2 layer, in chunks.

For one sequence, ``H`` heads of size ``P`` and a state of ``N`` values a
head dimension (``G`` groups of heads share ``B`` and ``C``)::

    h_t = exp(dt_t a) h_{t-1} + dt_t x_t (x) B_t        h: [P, N] a head, h_{-1} = 0
    y_t = C_t h_t

computed by the block decomposition of the state-space dual form
(arXiv:2405.21060, section 6): the sequence is cut into chunks of ``Q``
tokens; WITHIN a chunk ``y`` is a masked product
``((C B^T) * L) (dt x)`` with ``L[i, j] = exp(sum_{j < s <= i} dt_s a)`` for
``j <= i``; each chunk leaves ONE state (what its tokens add, decayed to
the chunk's end); the state each chunk starts from adds
``C_t h exp(sum_{s <= t} dt_s a)`` to ``y``. The sequential loop above is
``models/llm/nemotron_h_reference.py``'s; no form here shares code with it.

The decays, their running sums and the chunk states are float32 whatever
the compute type; the four products (``C B^T``, the masked one, a chunk's
state, the state's part of ``y``) take operands of the compute type and
accumulate in float32. Two forms, chosen by ``ops/dispatch.py``:

- **kernel** (a TPU, or the interpreter): one Pallas kernel each way.
  Grid ``(batch, group, chunk)``, the chunks walked in order with the
  state of the group's heads in VMEM scratch (``[heads a group x P, N]``
  float32); a step reads one chunk's ``x`` (``[Q, heads a group x P]``,
  lane-dense), ``dt`` (``[heads a group, Q]``, tokens on lanes), ``B`` and
  ``C`` and writes ``y``. The running sums, ``L``, ``C B^T``, the masked
  matrix and the carried state never leave VMEM. Heads are taken a SLAB
  at a time, as many as fill 128 lanes (two heads of 64): the masked
  product of each head runs over the whole slab and keeps its own lanes,
  which costs the MXU nothing (a half-filled pass is a pass), and the
  state's two products run over the slab at once. ``dt`` is folded into
  the masked matrix's columns (``(C B^T) * L * dt_j`` rounded once, where
  the chunked form rounds ``(C B^T) * L`` and ``dt x``). The running sum
  is a product with a 0/1 triangle at ``HIGHEST`` (float32 sums). The
  backward (a ``custom_vjp`` whose residuals are the inputs alone) first
  writes each chunk's entry state (``ssd_states``: float32 ``[B, chunks,
  H x P, N]``, a temporary of the backward), then walks the chunks in
  reverse carrying the state's cotangent in VMEM (``ssd_bwd``): ``dx``,
  ``dt``, ``B`` and ``C`` (summed over the group's heads inside), and
  ``a`` summed over the chunks. On a TPU a shape the blocks cannot tile
  raises.
- **chunked_xla** (elsewhere, and the kernel's reference): batched XLA
  products and fused elementwise passes over ``[H, Q, Q]`` arrays and a
  scan over chunk states, differentiated by JAX; the backward recomputes
  the inside (``jax.checkpoint``), so a layer keeps none of those arrays.

Both run inside ``jit(_ssd)``, so every operation of either direction,
the kernels' ``tpu_custom_call``s included, carries ``.../jit(_ssd)/...``
in its ``op_name``: what ``ssm_scan_time_pct`` and ``ssd_roofline`` read.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fedml_tpu.ops.dispatch import INTERPRET, REFERENCE, kernel_mode
from fedml_tpu.telemetry import get_tracer

_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))     # a @ b^T
_TN = (((0,), (0,)), ((), ()))     # a^T @ b
_VMEM_LIMIT = 64 * 1024 * 1024


def _chunked(x, dt, a, b, c, chunk: int):
    bsz, t, heads, p = x.shape
    groups, n = b.shape[2:]
    per, nc, f32 = heads // groups, t // chunk, jnp.float32
    dtype = x.dtype
    # [B, c, G, h, Q]: chunks, then heads by group, the chunk's tokens last
    by_head = lambda z: z.reshape(bsz, nc, chunk, groups, per).transpose(
        0, 1, 3, 4, 2)
    dt_h = by_head(dt.astype(f32))
    run = jnp.cumsum(dt_h * a.astype(f32).reshape(groups, per, 1), axis=-1)
    xdt = (x.astype(f32) * dt.astype(f32)[..., None]).reshape(
        bsz, nc, chunk, groups, per, p)
    bc = b.reshape(bsz, nc, chunk, groups, n)
    cc = c.reshape(bsz, nc, chunk, groups, n)

    # within a chunk: ((C B^T) * L) (dt x)
    scores = jnp.einsum("bcqgn,bcsgn->bcgqs", cc, bc,
                        preferred_element_type=f32)
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(lower, run[..., :, None] - run[..., None, :],
                              -jnp.inf))                       # [B,c,G,h,Q,Q]
    mixed = (scores[:, :, :, None] * decay).astype(dtype)
    y = jnp.einsum("bcghqs,bcsghp->bcqghp", mixed, xdt.astype(dtype),
                   preferred_element_type=f32)

    # what each chunk adds to the state, decayed to the chunk's end
    to_end = jnp.exp(run[..., -1:] - run)                      # [B,c,G,h,Q]
    added = jnp.einsum(
        "bcsgn,bcsghp->bcghpn", bc,
        (xdt * to_end.transpose(0, 1, 4, 2, 3)[..., None]).astype(dtype),
        preferred_element_type=f32)
    whole = jnp.exp(run[..., -1])                              # [B,c,G,h]

    def step(state, chunk_in):
        decay_c, added_c = chunk_in
        return decay_c[..., None, None] * state + added_c, state

    _, before = jax.lax.scan(
        step, jnp.zeros((bsz, groups, per, p, n), f32),
        (whole.transpose(1, 0, 2, 3), added.transpose(1, 0, 2, 3, 4, 5)))
    before = before.transpose(1, 0, 2, 3, 4, 5)               # [B,c,G,h,P,N]
    carried = jnp.einsum("bcqgn,bcghpn->bcqghp", cc, before.astype(dtype),
                         preferred_element_type=f32)
    y = y + carried * jnp.exp(run).transpose(0, 1, 4, 2, 3)[..., None]
    return y.reshape(bsz, t, heads, p).astype(dtype)


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------
class _Geometry(NamedTuple):
    chunk: int    # Q, tokens a grid step
    per: int      # heads a group (a grid step's heads)
    p: int        # head size
    n: int        # state size
    slab: int     # heads a slab: as many as fill 128 lanes, dividing ``per``

    @property
    def width(self) -> int:
        return self.slab * self.p

    @property
    def slabs(self) -> int:
        return self.per // self.slab


def _geometry(chunk: int, heads: int, p: int, groups: int,
              n: int) -> _Geometry:
    per = heads // groups
    fill = max(1, 128 // p)
    slab = max(k for k in range(1, per + 1) if per % k == 0 and k <= fill)
    return _Geometry(chunk, per, p, n, slab)


def _check_tiles(g: _Geometry, heads: int, t: int):
    """The compiled kernels' blocks: lanes in whole 128s (a slab of ``x``,
    ``B`` / ``C``'s state, ``dt``'s chunk of tokens), sublanes in whole 8s
    (``dt``'s heads of a group) unless a block is the whole axis."""
    needs = []
    if g.width % 128:
        needs.append(f"a slab of {g.slab} heads x {g.p} is not whole 128 "
                     "lanes")
    if g.n % 128:
        needs.append(f"state {g.n} is not whole 128 lanes")
    if g.chunk % 128 and g.chunk != t:
        needs.append(f"chunk {g.chunk} is not whole 128 lanes")
    if g.per % 8 and g.per != heads:
        needs.append(f"{g.per} heads a group are not whole 8 sublanes")
    if needs:
        raise ValueError("ssd: the kernel cannot tile this shape: "
                         + "; ".join(needs))


def _dot(a, b, dims=_NN, precision=None):
    return jax.lax.dot_general(a, b, dims, precision=precision,
                               preferred_element_type=_F32)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


class _Chunk(NamedTuple):
    """A step's float32 decays, for the group's heads: ``run`` [per, Q]
    (the running sum of ``dt a``, heads on sublanes) and ``run_t`` [Q, per]
    (the same, tokens on sublanes), ``dt`` and ``dt_t`` likewise; ``lower``
    the 0/1 triangle ``[i, s]``: ``s <= i``."""

    dt: jax.Array
    dt_t: jax.Array
    run: jax.Array
    run_t: jax.Array
    lower: jax.Array


def _chunk(dt_ref, a_ref, q: int) -> _Chunk:
    dt = dt_ref[...]                                   # [per, Q]
    dta = dt * a_ref[...]                              # a: [per, 1]
    lower = (_iota((q, q), 1) <= _iota((q, q), 0)).astype(_F32)  # [i, s]
    eye = (_iota((q, q), 1) == _iota((q, q), 0)).astype(_F32)
    # sums and transposes as products with 0/1 matrices, at float32 accuracy
    return _Chunk(dt, _dot(eye, dt, _NT, _HIGHEST),
                  _dot(dta, lower, _NT, _HIGHEST),
                  _dot(lower, dta, _NT, _HIGHEST), lower)


def _head(ch: _Chunk, hd: int, q: int):
    """Head ``hd``'s running sum as a row [1, Q], as a column [Q, 1], and at
    the chunk's end [1, 1]."""
    return ch.run[hd:hd + 1, :], ch.run_t[:, hd:hd + 1], \
        ch.run[hd:hd + 1, q - 1:q]


def _lanes(g: _Geometry, j: int):
    """[1, W] mask of the ``j``-th head's lanes in a slab."""
    lane = _iota((1, g.width), 1)
    return (lane >= j * g.p) & (lane < (j + 1) * g.p)


def _rows(g: _Geometry, j: int):
    """[W, 1] mask of the ``j``-th head's rows in a slab of the state."""
    row = _iota((g.width, 1), 0)
    return (row >= j * g.p) & (row < (j + 1) * g.p)


def _decay_matrix(ch: _Chunk, r_row, r_col):
    """``L``: exp of the running sum between ``j`` and ``i``, 0 past ``i``."""
    return jnp.exp(jnp.where(ch.lower > 0, r_col - r_row, -jnp.inf))


def _advance(g: _Geometry, ch: _Chunk, x_ref, b, state, s: int):
    """The state of slab ``s`` carried over the chunk: decayed to its end,
    plus what its tokens add (``(x dt exp(run_end - run))^T B``)."""
    q, lanes = g.chunk, slice(s * g.width, (s + 1) * g.width)
    xs = x_ref[:, lanes].astype(_F32)
    xw = jnp.zeros((q, g.width), _F32)
    decay = jnp.zeros((g.width, 1), _F32)
    for j in range(g.slab):
        hd = s * g.slab + j
        _, r_col, r_end = _head(ch, hd, q)
        xw = jnp.where(_lanes(g, j),
                       xs * (ch.dt_t[:, hd:hd + 1] * jnp.exp(r_end - r_col)),
                       xw)
        decay = jnp.where(_rows(g, j), jnp.exp(r_end), decay)
    state[lanes, :] = decay * state[lanes, :] + _dot(
        xw.astype(b.dtype), b, _TN)


def _fwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, state, *,
                g: _Geometry):
    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    q, cdt = g.chunk, x_ref.dtype
    ch = _chunk(dt_ref, a_ref, q)
    b, c = b_ref[...], c_ref[...]
    scores = _dot(c, b, _NT)                                  # C B^T [Q, Q]
    for s in range(g.slabs):
        lanes = slice(s * g.width, (s + 1) * g.width)
        xs = x_ref[:, lanes]
        carried = _dot(c, state[lanes, :].astype(cdt), _NT)  # C h^T [Q, W]
        y = jnp.zeros((q, g.width), _F32)
        for j in range(g.slab):
            hd = s * g.slab + j
            r_row, r_col, _ = _head(ch, hd, q)
            mixed = (scores * _decay_matrix(ch, r_row, r_col)
                     * ch.dt[hd:hd + 1, :]).astype(cdt)
            y = jnp.where(_lanes(g, j),
                          _dot(mixed, xs) + carried * jnp.exp(r_col), y)
        y_ref[:, lanes] = y.astype(y_ref.dtype)
        _advance(g, ch, x_ref, b, state, s)


def _states_kernel(x_ref, dt_ref, a_ref, b_ref, h_ref, state, *,
                   g: _Geometry):
    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    h_ref[...] = state[...]                  # the state the chunk starts from
    ch = _chunk(dt_ref, a_ref, g.chunk)
    b = b_ref[...]
    for s in range(g.slabs):
        _advance(g, ch, x_ref, b, state, s)


def _bwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, dy_ref, h_ref,
                dx_ref, ddt_ref, da_ref, db_ref, dc_ref, dstate, *,
                g: _Geometry):
    """One chunk, walked last to first. ``dstate`` holds the cotangent of
    the state the chunk leaves (zero after the last); ``h_ref`` the state it
    starts from. Per head, with ``E = exp(run)``, ``w = dt exp(run_end -
    run)``, ``M = (C B^T) * L * dt_j``::

        y = M x + E C h^T           h_end = exp(run_end) h + (w x)^T B

    A cotangent of the running sum is gathered per token, as a column
    (``[Q, per]``) or as a row (``[per, Q]``) by where its terms arise, and
    summed back to ``dt a`` by one product with a triangle."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)
        da_ref[...] = jnp.zeros_like(da_ref)

    q, cdt = g.chunk, x_ref.dtype
    ch = _chunk(dt_ref, a_ref, q)
    b, c = b_ref[...], c_ref[...]
    scores = _dot(c, b, _NT)
    d_scores = jnp.zeros((q, q), _F32)
    db = jnp.zeros((q, g.n), _F32)
    dc = jnp.zeros((q, g.n), _F32)
    head_lane = _iota((1, g.per), 1)
    head_row = _iota((g.per, 1), 0)
    last = _iota((q, 1), 0) == q - 1
    d_run_t = jnp.zeros((q, g.per), _F32)    # per token i (sublanes)
    d_run = jnp.zeros((g.per, q), _F32)      # per token j (lanes)
    ddt_t = jnp.zeros((q, g.per), _F32)
    ddt = jnp.zeros((g.per, q), _F32)
    for s in range(g.slabs):
        lanes = slice(s * g.width, (s + 1) * g.width)
        xs, dys = x_ref[:, lanes], dy_ref[:, lanes]
        xs32, dy32 = xs.astype(_F32), dys.astype(_F32)
        h, dh = h_ref[lanes, :], dstate[lanes, :]
        carried = _dot(c, h.astype(cdt), _NT)            # C h^T [Q, W]
        b_dh = _dot(b, dh.astype(cdt), _NT)              # B dh^T [Q, W]
        dx = jnp.zeros((q, g.width), _F32)
        e_lane = jnp.zeros((q, g.width), _F32)
        w_lane = jnp.zeros((q, g.width), _F32)
        decay = jnp.zeros((g.width, 1), _F32)
        for j in range(g.slab):
            hd = s * g.slab + j
            on, rows = _lanes(g, j), _rows(g, j)
            r_row, r_col, r_end = _head(ch, hd, q)
            dt_row = ch.dt[hd:hd + 1, :]
            lmat = _decay_matrix(ch, r_row, r_col)
            mixed = (scores * lmat * dt_row).astype(cdt)
            dy_j = jnp.where(on, dy32, 0.0).astype(cdt)
            d_mixed = _dot(dy_j, xs, _NT) * lmat            # dM * L
            dx = jnp.where(on, _dot(mixed, dys, _TN), dx)   # M^T dy
            d_scores = d_scores + d_mixed * dt_row
            d_l = d_mixed * scores                          # dL * L / dt_j
            ddt = jnp.where(head_row == hd,
                            jnp.sum(d_l, axis=0, keepdims=True), ddt)
            d_l = d_l * dt_row
            e = jnp.exp(r_col)
            to_end = jnp.exp(r_end - r_col)
            w = ch.dt_t[:, hd:hd + 1] * to_end
            dw = jnp.sum(jnp.where(on, xs32 * b_dh, 0.0), axis=1,
                         keepdims=True)                     # [Q, 1]
            h_dh = jnp.sum(jnp.where(rows, h * dh, 0.0), axis=1,
                           keepdims=True)
            at_end = (jnp.exp(r_end) * jnp.sum(h_dh, axis=0, keepdims=True)
                      + jnp.sum(dw * w, axis=0, keepdims=True))
            col = (jnp.sum(d_l, axis=1, keepdims=True)
                   + e * jnp.sum(jnp.where(on, dy32 * carried, 0.0), axis=1,
                                 keepdims=True)
                   - dw * w + jnp.where(last, at_end, 0.0))
            d_run_t = jnp.where(head_lane == hd, col, d_run_t)
            d_run = jnp.where(head_row == hd,
                              -jnp.sum(d_l, axis=0, keepdims=True), d_run)
            ddt_t = jnp.where(head_lane == hd, dw * to_end, ddt_t)
            e_lane = jnp.where(on, e, e_lane)
            w_lane = jnp.where(on, w, w_lane)
            decay = jnp.where(rows, jnp.exp(r_end), decay)
        e_dy = (dy32 * e_lane).astype(cdt)
        dc = dc + _dot(e_dy, h.astype(cdt))
        db = db + _dot((xs32 * w_lane).astype(cdt), dh.astype(cdt))
        dx_ref[:, lanes] = (dx + b_dh * w_lane).astype(dx_ref.dtype)
        dstate[lanes, :] = decay * dh + _dot(e_dy, c, _TN)
    d_scores = d_scores.astype(cdt)
    dc_ref[...] = (dc + _dot(d_scores, b)).astype(dc_ref.dtype)
    db_ref[...] = (db + _dot(d_scores, c, _TN)).astype(db_ref.dtype)
    # the running sum's cotangent summed from the chunk's end back: the
    # cotangent of ``dt a`` at token s is that of every run_i with i >= s
    eye = (_iota((q, q), 1) == _iota((q, q), 0)).astype(_F32)
    d_dta = (_dot(d_run_t, ch.lower, _TN, _HIGHEST)
             + _dot(d_run, ch.lower, _NN, _HIGHEST))
    ddt_ref[...] = (ddt + _dot(ddt_t, eye, _TN, _HIGHEST)
                    + a_ref[...] * d_dta)
    da_ref[...] += jnp.sum(ch.dt * d_dta, axis=1, keepdims=True)


def _specs(g: _Geometry, nc: int, reverse: bool):
    """BlockSpecs of a grid ``(batch, group, chunk)``: ``x``-like
    ``[B, T, H P]``, ``dt``-like ``[B, H, T]``, ``a``-like ``[H, 1]``,
    ``B``-like ``[B, T, G N]``, states ``[B, chunks, H P, N]``, ``da``
    ``[B, H, 1]``."""
    at = (lambda ci: nc - 1 - ci) if reverse else (lambda ci: ci)
    return dict(
        x=pl.BlockSpec((None, g.chunk, g.per * g.p),
                       lambda bi, gi, ci: (bi, at(ci), gi)),
        dt=pl.BlockSpec((None, g.per, g.chunk),
                        lambda bi, gi, ci: (bi, gi, at(ci))),
        a=pl.BlockSpec((g.per, 1), lambda bi, gi, ci: (gi, 0)),
        bc=pl.BlockSpec((None, g.chunk, g.n),
                        lambda bi, gi, ci: (bi, at(ci), gi)),
        state=pl.BlockSpec((None, None, g.per * g.p, g.n),
                           lambda bi, gi, ci: (bi, at(ci), gi, 0)),
        da=pl.BlockSpec((None, g.per, 1), lambda bi, gi, ci: (bi, gi, 0)))


def _call(kernel, name, g: _Geometry, grid, in_specs, out_specs, out_shape,
          interpret):
    return pl.pallas_call(
        functools.partial(kernel, g=g),
        grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((g.per * g.p, g.n), _F32)],
        # the chunk axis carries the state (or its cotangent)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name=name)


def _flat(x, dt, a, b, c):
    """The kernels' views: ``x`` [B, T, H P], ``dt`` [B, H, T] float32,
    ``a`` [H, 1] float32, ``B`` / ``C`` [B, T, G N]."""
    bsz, t, heads, p = x.shape
    groups, n = b.shape[2:]
    return (x.reshape(bsz, t, heads * p),
            jnp.swapaxes(dt.astype(_F32), 1, 2),
            a.astype(_F32).reshape(heads, 1),
            b.reshape(bsz, t, groups * n), c.reshape(bsz, t, groups * n))


# One signature a layer: under ``jit`` each body is traced and lowered once
# for all the layers that share it, not once a layer.
@functools.partial(jax.jit, static_argnums=(5, 6))
def _fwd(x, dt, a, b, c, chunk, interpret):
    bsz, t, heads, p = x.shape
    groups, n = b.shape[2:]
    g, nc = _geometry(chunk, heads, p, groups, n), t // chunk
    sp = _specs(g, nc, reverse=False)
    xf, dtf, af, bf, cf = _flat(x, dt, a, b, c)
    y = _call(_fwd_kernel, "ssd_fwd", g, (bsz, groups, nc),
              [sp["x"], sp["dt"], sp["a"], sp["bc"], sp["bc"]], sp["x"],
              jax.ShapeDtypeStruct(xf.shape, x.dtype), interpret)(
        xf, dtf, af, bf, cf)
    return y.reshape(x.shape)


@functools.partial(jax.jit, static_argnums=(5, 6))
def _bwd(x, dt, a, b, c, chunk, interpret, dy):
    bsz, t, heads, p = x.shape
    groups, n = b.shape[2:]
    g, nc = _geometry(chunk, heads, p, groups, n), t // chunk
    xf, dtf, af, bf, cf = _flat(x, dt, a, b, c)
    fwd, rev = _specs(g, nc, reverse=False), _specs(g, nc, reverse=True)
    states = _call(
        _states_kernel, "ssd_states", g, (bsz, groups, nc),
        [fwd["x"], fwd["dt"], fwd["a"], fwd["bc"]], fwd["state"],
        jax.ShapeDtypeStruct((bsz, nc, heads * p, n), _F32), interpret)(
        xf, dtf, af, bf)
    dx, ddt, da, db, dc = _call(
        _bwd_kernel, "ssd_bwd", g, (bsz, groups, nc),
        [rev["x"], rev["dt"], rev["a"], rev["bc"], rev["bc"], rev["x"],
         rev["state"]],
        [rev["x"], rev["dt"], rev["da"], rev["bc"], rev["bc"]],
        [jax.ShapeDtypeStruct(xf.shape, x.dtype),
         jax.ShapeDtypeStruct(dtf.shape, _F32),
         jax.ShapeDtypeStruct((bsz, heads, 1), _F32),
         jax.ShapeDtypeStruct(bf.shape, b.dtype),
         jax.ShapeDtypeStruct(cf.shape, c.dtype)], interpret)(
        xf, dtf, af, bf, cf, dy.reshape(xf.shape), states)
    return (dx.reshape(x.shape), jnp.swapaxes(ddt, 1, 2).astype(dt.dtype),
            jnp.sum(da[..., 0], axis=0).astype(a.dtype),
            db.reshape(b.shape), dc.reshape(c.shape))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _kernel(x, dt, a, b, c, chunk, interpret):
    return _fwd(x, dt, a, b, c, chunk, interpret)


def _kernel_fwd(x, dt, a, b, c, chunk, interpret):
    # the inputs alone: what jax.checkpoint keeps of the chunked form
    return _fwd(x, dt, a, b, c, chunk, interpret), (x, dt, a, b, c)


def _kernel_bwd(chunk, interpret, res, dy):
    return _bwd(*res, chunk, interpret, dy)


_kernel.defvjp(_kernel_fwd, _kernel_bwd)


@functools.partial(jax.jit, static_argnums=(5, 6))
def _ssd(x, dt, a, b, c, chunk, form):
    with jax.named_scope("ssd"):
        if form == REFERENCE:
            return jax.checkpoint(functools.partial(_chunked, chunk=chunk))(
                x, dt, a, b, c)
        return _kernel(x, dt, a, b, c, chunk, form == INTERPRET)


def ssd(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
        c: jax.Array, chunk: int = 128,
        interpret: Optional[bool] = None) -> jax.Array:
    """``y`` ``[B, T, H, P]`` of the recurrence above.

    ``x`` ``[B, T, H, P]``; ``dt`` ``[B, T, H]`` (positive: after its
    softplus); ``a`` ``[H]`` (negative); ``b``, ``c`` ``[B, T, G, N]`` with
    ``H`` a multiple of ``G``. A ``T`` that is not a whole number of chunks
    is padded with tokens of ``dt = 0`` (they decay nothing and add
    nothing) and cut again. ``D x`` (the skip) is the caller's.

    ``interpret=None`` (model code): the kernels on a TPU, the chunked XLA
    form elsewhere; ``interpret=True`` runs the kernels under the Pallas
    interpreter; ``interpret=False`` always emits the compiled kernels —
    see :mod:`fedml_tpu.ops.dispatch`.
    """
    bsz, t, heads, p = x.shape
    groups, n = b.shape[2:]
    if heads % groups:
        raise ValueError(f"ssd: {heads} heads are not a multiple of "
                         f"{groups} groups")
    chunk = min(chunk, t)
    pad = -t % chunk
    mode = kernel_mode(interpret, off_tpu=REFERENCE)
    chunks = (t + pad) // chunk
    if mode == REFERENCE:
        plan = dict(form="chunked_xla", heads_per_step=heads,
                    state_bytes=bsz * chunks * heads * p * n * 4)
    else:
        g = _geometry(chunk, heads, p, groups, n)
        if mode != INTERPRET:
            _check_tiles(g, heads, t + pad)
        plan = dict(form="kernel", heads_per_step=g.per,
                    state_bytes=g.per * p * n * 4)
    get_tracer().event(
        "ssd/plan", rows=bsz * t, heads=heads, head_dim=p, groups=groups,
        state=n, chunk=chunk, chunks=chunks, block_t=chunk, **plan,
        dtype=jnp.dtype(x.dtype).name)
    if pad:
        grow = lambda z: jnp.pad(z, [(0, 0), (0, pad)] + [(0, 0)] * (z.ndim - 2))
        x, dt, b, c = grow(x), grow(dt), grow(b), grow(c)
    return _ssd(x, dt, a, b, c, chunk, mode)[:, :t]
