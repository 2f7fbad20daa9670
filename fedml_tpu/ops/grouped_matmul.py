"""Pallas TPU grouped matrix product — the dropless expert layer's hot op.

A router over frozen experts sends each of a step's tokens to ``k`` of
them, of which this layer HOLDS a range (all of them, or the chip's share
of an expert-parallel deployment). Sorted by expert the held assignments
are runs of rows, and each run is multiplied by its own expert's matrix:
``out[r] = x[r] @ w[e(r)]``. No capacity factor, no assignment dropped, no
one-hot dispatch tensor; an assignment to an expert that is not held is
skipped (another chip's work).

Layout (:func:`group_layout`): every run is padded to a whole number of
``block_m``-row tiles, so a row tile belongs to exactly ONE expert and the
kernel is a plain tiled product whose weight block is chosen per row tile
by a scalar-prefetched table. The padded buffer has ``padded_rows(a, E,
block_m)`` rows whatever the routing is (shapes stay static; ``a`` the most
assignments that can be held, every one of every token's choices); the tiles
past the last run are neither computed nor fetched (their x and output
block indices repeat the last live tile's, so the pipeline issues no copy,
and the kernel asks for no weights there) and their rows of the output stay
unwritten — nothing reads them, since the way back (:func:`combine`)
gathers each token's own row.

Grid ``(column tiles, row tiles)`` with the row tiles innermost: consecutive
row tiles of one expert (a RUN) multiply by the same weight block, which is
copied once per expert and column tile — the product reads every live
expert's matrix once, which is the whole cost at B1 (an expert sees tens of
rows and its 2048x2048 matrix is 8 MB). The contraction is not tiled: a
block holds the full contracted width (``[block_m, K] x [K, block_n]``), so
there is no accumulator and no revisit.

The weights' schedule is the kernel's own: the expert stack stays in HBM
and the kernel holds ``WEIGHT_SLOTS`` = 2 blocks in VMEM (what Pallas's
double buffer held), one copy semaphore a slot. At the FIRST tile of a run
it asks for the NEXT run's block — found by a scalar walk down the table
from the next tile until the expert changes; past the last live tile it is
the first run of the next column tile — into the slot the run before has
just left, then waits for its own, which was asked for a whole run ago. So
a copy has the ``r`` products of a run of ``r`` tiles to hide under (Pallas
asks for a grid step's blocks one step ahead: one product, whatever ``r``;
at 256 rows an expert, where a block's copy takes as long as three
products, copies and products then ADD: PERF.md, PR 38), and a run of one
tile asks at that tile: the step-ahead schedule, to the step. The copy goes
to the background queue (``priority=1``): the pipeline's x tile for the
next grid step is asked for after it and needed first. Dead tiles start and
wait for nothing; a call with no live tile issues no copy; every copy
started is waited for by the run it is for. Both grid axes carry that state
(the slot in use, what is in flight — across a column tile's end too), so
both are ``arbitrary``: neither may be split over cores.

The experts are frozen: the custom VJP gives the gradient with respect to
the ROWS only (``dx = dy @ w[e]^T``, the same kernel contracting over the
matrix's last axis, so no transposed copy of the weights is ever made) and
none for the weights.

The elementwise function that stands BEFORE a product is the product's to
apply (``activation=``, an :class:`Activation` the calling family states:
its value and its derivative): ``out[r] = f(x[r]) @ w[e(r)]``. Forward,
``f`` is the kernel's prologue on the x tile it has just fetched (from the
tile's own type through float32, rounded once: what ``f(x)`` written
outside would have fed the product); backward, ``f'`` of the saved ``x``
is the epilogue of ``moe_gmm_t`` on the float32 product before its one
cast (``dx = (dy @ w[e]^T) * f'(x)``, ``x`` a third operand tiled like the
output). Neither ``f(x)`` nor its cotangent is ever an array: a fusion
outside the kernels walks the whole padded buffer because its shape is
static, the kernels walk the live tiles. The dead tiles' rows of ``dx``
stay unwritten like those of ``out``: :func:`combine`'s transpose hands
exact zeros to every padding row and :func:`dispatch`'s selects by
``held``, so nothing reads them. Without an activation the call is the
plain product, kernel body and operands unchanged. Every product traced
leaves a ``moe_gmm/plan`` point event (:func:`_plan`).

Which form a call gets is decided in
``ops/dispatch.py``: compiled on a TPU or an exception; off-TPU the plain
XLA reference (:func:`reference_grouped_matmul`), or the interpreter with
``interpret=True``. (``jax.lax.ragged_dot`` was tried first: the chip's
compiler has a kernel for it, but its row gradient makes a transposed
copy of every expert's matrix; 0.51 + 0.90 ms a product pair against
0.27 + 0.26 here, PERF.md.)
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fedml_tpu.ops.dispatch import (
    COMPILED,
    INTERPRET,
    REFERENCE,
    kernel_mode,
)
from fedml_tpu.telemetry import get_tracer

# rows of a tile: an expert at B1 T1024 sees 64 rows on average, and every
# run is padded to whole tiles, so a tile much larger than a run multiplies
# mostly padding and a smaller one pays more grid steps; 16 is the least a
# bfloat16 tile can hold (chip readings of 16 to 256 rows in PERF.md)
BLOCK_M = 64
# the widest column tile; the tile itself is chosen from N (column_tile)
BLOCK_N = 1024
_VMEM_LIMIT = 64 * 1024 * 1024
# weight blocks the kernel holds: the run being multiplied and the next
# run's, in flight (what Pallas's own double buffer held)
WEIGHT_SLOTS = 2


class GroupLayout(NamedTuple):
    """Where each held assignment's row lives in the padded, expert-sorted
    buffer. An assignment is ``(token t, choice j)``, numbered ``t * k + j``;
    with one choice a token (``expert`` given as ``[m]``) it is the token."""

    src: jax.Array         # [P] assignment a padded row is read from (any, if pad)
    valid: jax.Array       # [P] bool: the row holds an assignment
    pos: jax.Array         # [m] or [m, k] row holding the assignment (any, if not held)
    held: jax.Array        # [m] or [m, k] bool: the assignment's expert is held here
    tile_group: jax.Array  # [P / block_m] held expert (0-based) of each row tile
    live_tiles: jax.Array  # [1] row tiles that hold any assignment
    counts: jax.Array      # [E] assignments of each held expert


@dataclasses.dataclass(frozen=True)
class Activation:
    """An elementwise function a family puts before a grouped product:
    ``value`` and ``derivative`` are ``jax.numpy`` on float32 arrays of any
    shape (they run on a tile inside the kernels), ``name`` is what the
    ``moe_gmm/plan`` event says."""

    name: str
    value: Callable[[jax.Array], jax.Array]
    derivative: Callable[[jax.Array], jax.Array]


def padded_rows(m: int, groups: int, block_m: int) -> int:
    """Rows of the padded buffer: every run rounded up to whole tiles."""
    worst = m + groups * (block_m - 1)
    return -(-worst // block_m) * block_m


def group_layout(expert: jax.Array, groups: int, block_m: int = BLOCK_M,
                 first: int = 0) -> GroupLayout:
    """The layout for ``expert``: each token's expert index ``[m]``, or its
    ``k`` DISTINCT choices ``[m, k]``, of which the ``groups`` experts from
    ``first`` on are held here and placed; the others are skipped.

    No sort and no scatter (both serialise on the chip: 0.45 ms a layer at
    1,024 tokens, a fifth of a step; PERF.md). A token chooses an expert at
    most once, so an assignment's rank within its expert's run is the
    number of earlier tokens that chose the expert: a product of the
    strictly lower triangle with the ``[m, E]`` table of who chose whom
    (0 / 1 operands, float32 sums: exact; linear in held experts whatever
    ``k``), and the assignment of each padded row is found by comparing
    the row's number with the positions of its own expert's column, a
    ``[P, m]`` grid that fuses into one reduction."""
    flat = expert.ndim == 1
    chosen = (expert[:, None] if flat else expert) - first          # [m, k]
    m, k = chosen.shape
    rows = padded_rows(m * min(k, groups), groups, block_m)
    tiles = rows // block_m
    token = jnp.arange(m, dtype=jnp.int32)
    picks = chosen[:, :, None] == jnp.arange(groups, dtype=jnp.int32)  # [m, k, E]
    mine = jnp.any(picks, axis=1)                                   # [m, E]
    counts = jnp.sum(mine, axis=0, dtype=jnp.int32)
    run_tiles = -(-counts // block_m)
    tile_end = jnp.cumsum(run_tiles)                    # [E] in tiles
    run_start = (tile_end - run_tiles) * block_m        # [E] in rows
    earlier = (token[:, None] > token[None, :]).astype(jnp.bfloat16)
    rank = jnp.dot(earlier, mine.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32).astype(jnp.int32)
    # row of (token, held expert); -1 where the token did not choose it
    place = jnp.where(mine, run_start + rank, -1)
    held = jnp.any(picks, axis=2)                                   # [m, k]
    pos = jnp.sum(jnp.where(picks, place[:, None, :], 0), axis=2)   # [m, k]
    live = tile_end[-1]
    tile = jnp.clip(jnp.arange(tiles, dtype=jnp.int32), 0,
                    jnp.maximum(live, 1) - 1)
    tile_group = jnp.minimum(
        jnp.sum(tile_end[None, :] <= tile[:, None], axis=1, dtype=jnp.int32),
        groups - 1)
    row = jnp.arange(rows, dtype=jnp.int32).reshape(tiles, block_m, 1)
    # the assignment (t * k + j) of each (token, held expert), and each row
    # tile's own expert's column of both tables
    slot = jnp.sum(jnp.where(
        picks, jnp.arange(k, dtype=jnp.int32)[:, None], 0), axis=1)
    number = token[:, None] * k + slot                              # [m, E]
    hit = place.T[tile_group][:, None, :] == row               # [P/bm, bm, m]
    src = jnp.sum(jnp.where(hit, number.T[tile_group][:, None, :], 0), axis=2)
    valid = jnp.any(hit, axis=2)
    if flat:
        pos, held = pos[:, 0], held[:, 0]
    return GroupLayout(src.reshape(rows), valid.reshape(rows), pos, held,
                       tile_group, live[None], counts)


# -- the way in and the way back: permutations, so their transposes are
# gathers too (a scatter-add serialises on the chip) -----------------------
@jax.custom_vjp
def dispatch(x, layout: GroupLayout):
    """``x`` ``[m, K]`` into the padded buffer ``[P, K]``."""
    return x[_token(layout)]


def _token(layout: GroupLayout):
    """The token of each padded row (``src`` numbers assignments)."""
    if layout.pos.ndim == 1:
        return layout.src
    return layout.src // layout.pos.shape[1]


def _dispatch_fwd(x, layout):
    return dispatch(x, layout), layout


def _dispatch_bwd(layout, g):
    # a row that was never written (not held: no row is its own) may hold
    # anything, so it is selected away, not multiplied away
    held = jnp.where(layout.held[..., None], g[layout.pos], 0)
    return (held if held.ndim == 2 else jnp.sum(held, axis=1)), None


dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def combine(y, layout: GroupLayout):
    """Each assignment's own row of the padded buffer: ``[P, N] -> [m, N]``
    or ``[m, k, N]``, zeros for an assignment that is not held here."""
    return jnp.where(layout.held[..., None], y[layout.pos], 0)


def _combine_fwd(y, layout):
    return combine(y, layout), layout


def _combine_bwd(layout, g):
    # padding rows get an exact zero: the backward product reads them
    g = g.reshape(-1, g.shape[-1])
    return jnp.where(layout.valid[:, None], g[layout.src], 0), None


combine.defvjp(_combine_fwd, _combine_bwd)


# -- the kernel ------------------------------------------------------------
def _kernel(tile_group, live_tiles, x_ref, w_hbm, *refs, transpose_rhs,
            activation, block_n):
    """One live tile's product, and at the FIRST tile of a run the weights'
    schedule: start the copy of the next run's block into the other slot,
    then wait for this run's (module docstring). With an ``activation``:
    its value on the x tile before the product, or (``transpose_rhs``, the
    row gradient) its derivative of the saved tile ``refs[0]`` on the
    product after it."""
    *saved, o_ref, w_buf, arrived, slot = refs
    j, i = pl.program_id(0), pl.program_id(1)
    live = live_tiles[0]

    def block_copy(expert, column, s):
        columns = pl.ds(column * block_n, block_n)
        block = w_hbm.at[expert, columns, :] if transpose_rhs \
            else w_hbm.at[expert, :, columns]
        return pltpu.make_async_copy(block, w_buf.at[s], arrived.at[s])

    @pl.when(i < live)
    def _():
        expert = tile_group[i]

        @pl.when((i == 0) | (tile_group[jnp.maximum(i - 1, 0)] != expert))
        def _():
            # the call's first block has nothing to hide under; it goes
            # to slot 0, where the flip below lands
            @pl.when((j == 0) & (i == 0))
            def _():
                slot[0] = 1
                block_copy(expert, j, 0).start()

            here = 1 - slot[0]
            slot[0] = here
            # the next run: the first later live tile of another expert,
            # or past the last one the next column tile's first run
            last = tile_group.shape[0] - 1
            nxt = jax.lax.while_loop(
                lambda t: (t < live)
                & (tile_group[jnp.minimum(t, last)] == expert),
                lambda t: t + 1, i + 1)
            wraps = nxt >= live

            # asked for BEFORE this run's block is waited for (the other
            # slot's run ended a grid step ago), so the copy engine always
            # has a block queued; in the background queue, so the next
            # grid step's x tile does not wait behind it
            @pl.when(~wraps | (j + 1 < pl.num_programs(0)))
            def _():
                block_copy(tile_group[jnp.where(wraps, 0, nxt)],
                           jnp.where(wraps, j + 1, j),
                           1 - here).start(priority=1)

            block_copy(expert, j, here).wait()

        x = x_ref[...]
        if activation is not None and not transpose_rhs:
            x = activation.value(x.astype(jnp.float32)).astype(x.dtype)
        contract = ((1,), (1,)) if transpose_rhs else ((1,), (0,))
        out = jax.lax.dot_general(
            x, w_buf[slot[0]], (contract, ((), ())),
            preferred_element_type=jnp.float32)
        if saved:
            out = out * activation.derivative(
                saved[0][...].astype(jnp.float32))
        o_ref[...] = out.astype(o_ref.dtype)


def column_tile(n: int, at_most: int = BLOCK_N) -> int:
    """The column tile for ``n`` columns: ``n`` itself if it fits, else the
    widest whole number of 128 lanes that divides ``n`` and is ``at_most``
    (2688 = 21 x 128 gets 896, 2048 gets 1024)."""
    if n <= at_most:
        return n
    fits = [c for c in range(128, at_most + 1, 128) if n % c == 0]
    if n % at_most == 0:
        fits.append(at_most)
    if not fits:
        raise ValueError(
            f"moe_gmm: {n} columns have no tile of whole 128-lane groups "
            f"up to {at_most}")
    return max(fits)


def _plan(x, n, transpose_rhs, block_m, block_n, activation, form):
    """One ``moe_gmm/plan`` point event: a grouped product was traced (the
    forward by :func:`grouped_matmul`, the row gradient by the custom
    VJP's rule, which the plain-XLA reference form does not have)."""
    rows, k = x.shape
    block_n = column_tile(n, block_n)
    get_tracer().event(
        "moe_gmm/plan", rows=rows, k=k, n=n, block_m=block_m,
        block_n=block_n, row_tiles=rows // block_m, column_tiles=n // block_n,
        transpose=transpose_rhs,
        activation=activation.name if activation else None,
        weight_prefetch="run", weight_slots=WEIGHT_SLOTS,
        dtype=jnp.dtype(x.dtype).name, form=form)


@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8, 9))
def _gmm(x, w, tile_group, live_tiles, saved, transpose_rhs, block_m, block_n,
         interpret, activation):
    """``saved``: the activation's input where this is the row gradient of
    a product that took one (its derivative is the epilogue), else None."""
    rows, k = x.shape
    n = w.shape[1] if transpose_rhs else w.shape[2]
    block_n = column_tile(n, block_n)
    if rows % block_m:
        raise ValueError(f"moe_gmm: rows {rows} must be a whole multiple "
                         f"of the block ({block_m})")

    def row_tile(j, i, tile_group, live_tiles):
        # no live tile at all (nothing routed to an expert held here): 0
        return jnp.maximum(jnp.minimum(i, live_tiles[0] - 1), 0)

    x_spec = pl.BlockSpec(
        (block_m, k), lambda j, i, tg, lt: (row_tile(j, i, tg, lt), 0))
    # w [E, n, k] (transposed: contract over its last axis) or [E, k, n]
    # stays where it is; the kernel copies a block a run into its two slots
    block = (block_n, k) if transpose_rhs else (k, block_n)
    o_spec = pl.BlockSpec(
        (block_m, block_n), lambda j, i, tg, lt: (row_tile(j, i, tg, lt), j))
    operands, in_specs = [x, w], [x_spec, pl.BlockSpec(memory_space=pl.ANY)]
    if saved is not None:   # tiled like the output it scales
        operands, in_specs = operands + [saved], in_specs + [o_spec]
    return pl.pallas_call(
        functools.partial(_kernel, transpose_rhs=transpose_rhs,
                          activation=activation, block_n=block_n),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n // block_n, rows // block_m),
            in_specs=in_specs, out_specs=o_spec,
            scratch_shapes=[
                pltpu.VMEM((WEIGHT_SLOTS, *block), w.dtype),
                pltpu.SemaphoreType.DMA((WEIGHT_SLOTS,)),
                pltpu.SMEM((1,), jnp.int32)]),   # the current run's slot
        out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
        # both axes carry state: the slots and what is in flight pass from
        # row tile to row tile and from a column tile's last run to the
        # next one's first, so neither may be split over cores
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="moe_gmm_t" if transpose_rhs else "moe_gmm",
    )(tile_group, live_tiles, *operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _grouped(x, w, tile_group, live_tiles, block_m, block_n, interpret,
             activation):
    return _gmm(x, w, tile_group, live_tiles, None, False, block_m, block_n,
                interpret, activation)


def _grouped_fwd(x, w, tile_group, live_tiles, block_m, block_n, interpret,
                 activation):
    out = _grouped(x, w, tile_group, live_tiles, block_m, block_n, interpret,
                   activation)
    # an activation's derivative wants its input; the plain product keeps
    # no rows at all
    saved = None if activation is None else x
    return out, (saved, w, tile_group, live_tiles)


def _grouped_bwd(block_m, block_n, interpret, activation, res, dy):
    saved, w, tile_group, live_tiles = res
    _plan(dy, w.shape[1], True, block_m, block_n, activation,
          INTERPRET if interpret else COMPILED)
    dx = _gmm(dy, w, tile_group, live_tiles, saved, True, block_m, block_n,
              interpret, activation)
    # the experts are frozen: no product for their gradient is ever built
    return dx, None, None, None


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _activate(activation: Activation, x):
    """``f(x)`` as the kernels compute it, and THEIR derivative (the
    family's own, not JAX's of ``value``) for the reference form."""
    return activation.value(x.astype(jnp.float32)).astype(x.dtype)


def _activate_fwd(activation, x):
    return _activate(activation, x), x


def _activate_bwd(activation, x, g):
    scaled = g.astype(jnp.float32) * activation.derivative(
        x.astype(jnp.float32))
    return (scaled.astype(x.dtype),)


_activate.defvjp(_activate_fwd, _activate_bwd)


def reference_grouped_matmul(x, w, layout: GroupLayout, block_m: int,
                             activation: Optional[Activation] = None):
    """Plain XLA: each row tile times its own expert's matrix, gathered
    (every tile, the dead ones too)."""
    if activation is not None:
        x = _activate(activation, x)
    tiles = x.shape[0] // block_m
    xt = x.reshape(tiles, block_m, x.shape[1])
    out = jnp.einsum("tmk,tkn->tmn", xt, w[layout.tile_group],
                     preferred_element_type=jnp.float32)
    return out.reshape(x.shape[0], w.shape[2]).astype(x.dtype)


def grouped_matmul(x: jax.Array, w: jax.Array, layout: GroupLayout,
                   block_m: int = BLOCK_M, block_n: int = BLOCK_N,
                   interpret: Optional[bool] = None,
                   activation: Optional[Activation] = None) -> jax.Array:
    """``out[r] = f(x[r]) @ w[expert of r's tile]``; x ``[P, K]`` in the
    layout's padded order, w ``[E, K, N]`` (frozen: it gets no gradient),
    ``f`` the ``activation`` (none: the plain product), applied and
    differentiated inside the kernels on live tiles only.

    The rows of dead tiles are unwritten in ``out`` and in the row
    gradient; see the module's docstring for why nothing reads them.
    ``block_m`` must be the layout's. ``interpret`` as in
    :func:`fedml_tpu.ops.flash_attention.flash_attention`.
    """
    mode = kernel_mode(interpret, off_tpu=REFERENCE)
    _plan(x, w.shape[2], False, block_m, block_n, activation, mode)
    if mode == REFERENCE:
        return reference_grouped_matmul(
            x, jax.lax.stop_gradient(w), layout, block_m, activation)
    return _grouped(x, w, layout.tile_group, layout.live_tiles, block_m,
                    block_n, mode == INTERPRET, activation)
