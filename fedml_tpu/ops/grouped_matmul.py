"""Pallas TPU grouped matrix product — the dropless expert layer's hot op.

A top-1 router over ``E`` frozen experts sends each of a step's tokens to
one expert. Sorted by expert the tokens are ``E`` runs of rows, and each
run is multiplied by its own expert's matrix: ``out[r] = x[r] @ w[e(r)]``.
No capacity, no token dropped, no one-hot dispatch tensor.

Layout (:func:`group_layout`): every run is padded to a whole number of
``block_m``-row tiles, so a row tile belongs to exactly ONE expert and the
kernel is a plain tiled product whose weight block is chosen per row tile
by a scalar-prefetched table. The padded buffer has ``padded_rows(m, E,
block_m)`` rows whatever the routing is (shapes stay static); the tiles
past the last run are neither computed nor fetched (their block indices
repeat the last live tile's, so the pipeline issues no copy) and their
rows of the output stay unwritten — nothing reads them, since the way back
(:func:`combine`) gathers each token's own row.

Grid ``(column tiles, row tiles)`` with the row tiles innermost: consecutive
row tiles of one expert name the same weight block, which is then copied
once per expert and column tile — the product reads every live expert's
matrix once, which is the whole cost at B1 (an expert sees tens of rows and
its 2048x2048 matrix is 8 MB). The contraction is not tiled: a block holds
the full contracted width (``[block_m, K] x [K, block_n]``), so there is no
accumulator and no revisit.

The experts are frozen: the custom VJP gives the gradient with respect to
the ROWS only (``dx = dy @ w[e]^T``, the same kernel contracting over the
matrix's last axis, so no transposed copy of the weights is ever made) and
none for the weights. Which form a call gets is decided in
``ops/dispatch.py``: compiled on a TPU or an exception; off-TPU the plain
XLA reference (:func:`reference_grouped_matmul`), or the interpreter with
``interpret=True``. (``jax.lax.ragged_dot`` was tried first: the chip's
compiler has a kernel for it, but its row gradient makes a transposed
copy of every expert's matrix; 0.51 + 0.90 ms a product pair against
0.27 + 0.26 here, PERF.md.)
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fedml_tpu.ops.dispatch import INTERPRET, REFERENCE, kernel_mode

# rows of a tile: an expert at B1 T1024 sees 64 rows on average, and every
# run is padded to whole tiles, so a tile much larger than a run multiplies
# mostly padding and a smaller one pays more grid steps; 16 is the least a
# bfloat16 tile can hold (chip readings of 16 to 256 rows in PERF.md)
BLOCK_M = 64
BLOCK_N = 1024
_VMEM_LIMIT = 64 * 1024 * 1024


class GroupLayout(NamedTuple):
    """Where each token's row lives in the padded, expert-sorted buffer."""

    src: jax.Array         # [P] token a padded row is read from (any, if pad)
    valid: jax.Array       # [P] bool: the row holds a token
    pos: jax.Array         # [m] row of the padded buffer holding token t
    tile_group: jax.Array  # [P / block_m] expert of each row tile
    live_tiles: jax.Array  # [1] row tiles that hold any token
    counts: jax.Array      # [E] tokens of each expert


def padded_rows(m: int, groups: int, block_m: int) -> int:
    """Rows of the padded buffer: every run rounded up to whole tiles."""
    worst = m + groups * (block_m - 1)
    return -(-worst // block_m) * block_m


def group_layout(expert: jax.Array, groups: int,
                 block_m: int = BLOCK_M) -> GroupLayout:
    """The layout for ``expert`` ``[m]`` (each token's expert index).

    No sort and no scatter (both serialise on the chip: 0.45 ms a layer at
    1,024 tokens, a fifth of a step; PERF.md): a token's rank within its
    expert's run, and the token of each padded row, are counted by
    comparisons over ``[m, m]`` and ``[P, m]`` index grids that fuse into
    one reduction each."""
    m = expert.shape[0]
    rows = padded_rows(m, groups, block_m)
    tiles = rows // block_m
    token = jnp.arange(m, dtype=jnp.int32)
    mine = expert[:, None] == jnp.arange(groups, dtype=jnp.int32)   # [m, E]
    counts = jnp.sum(mine, axis=0, dtype=jnp.int32)
    run_tiles = -(-counts // block_m)
    tile_end = jnp.cumsum(run_tiles)                    # [E] in tiles
    run_start = (tile_end - run_tiles) * block_m        # [E] in rows
    # tokens before t that go to t's expert
    rank = jnp.sum((expert[None, :] == expert[:, None])
                   & (token[None, :] < token[:, None]), axis=1,
                   dtype=jnp.int32)
    pos = jnp.sum(jnp.where(mine, run_start, 0), axis=1) + rank
    hit = pos[None, :] == jnp.arange(rows, dtype=jnp.int32)[:, None]  # [P, m]
    src = jnp.sum(jnp.where(hit, token, 0), axis=1)
    valid = jnp.any(hit, axis=1)
    live = tile_end[-1]
    tile = jnp.minimum(jnp.arange(tiles, dtype=jnp.int32), live - 1)
    tile_group = jnp.sum(tile_end[None, :] <= tile[:, None], axis=1,
                         dtype=jnp.int32)
    return GroupLayout(src, valid, pos, tile_group, live[None], counts)


# -- the way in and the way back: permutations, so their transposes are
# gathers too (a scatter-add serialises on the chip) -----------------------
@jax.custom_vjp
def dispatch(x, layout: GroupLayout):
    """``x`` ``[m, K]`` into the padded buffer ``[P, K]``."""
    return x[layout.src]


def _dispatch_fwd(x, layout):
    return dispatch(x, layout), layout


def _dispatch_bwd(layout, g):
    return g[layout.pos], None


dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def combine(y, layout: GroupLayout):
    """Each token's own row of the padded buffer: ``[P, N] -> [m, N]``."""
    return y[layout.pos]


def _combine_fwd(y, layout):
    return combine(y, layout), layout


def _combine_bwd(layout, g):
    # padding rows get an exact zero: the backward product reads them
    return jnp.where(layout.valid[:, None], g[layout.src], 0), None


combine.defvjp(_combine_fwd, _combine_bwd)


# -- the kernel ------------------------------------------------------------
def _kernel(tile_group, live_tiles, x_ref, w_ref, o_ref, *, transpose_rhs):
    del tile_group

    @pl.when(pl.program_id(1) < live_tiles[0])
    def _():
        contract = ((1,), (1,)) if transpose_rhs else ((1,), (0,))
        o_ref[...] = jax.lax.dot_general(
            x_ref[...], w_ref[...], (contract, ((), ())),
            preferred_element_type=jnp.float32).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7))
def _gmm(x, w, tile_group, live_tiles, transpose_rhs, block_m, block_n,
         interpret):
    rows, k = x.shape
    n = w.shape[1] if transpose_rhs else w.shape[2]
    block_n = min(block_n, n)
    if rows % block_m or n % block_n:
        raise ValueError(
            f"moe_gmm: rows {rows} and columns {n} must be whole multiples "
            f"of the blocks ({block_m}, {block_n})")

    def row_tile(j, i, tile_group, live_tiles):
        return jnp.minimum(i, live_tiles[0] - 1)

    x_spec = pl.BlockSpec(
        (block_m, k), lambda j, i, tg, lt: (row_tile(j, i, tg, lt), 0))
    if transpose_rhs:   # w [E, n, k]: contract over its last axis
        w_spec = pl.BlockSpec((None, block_n, k),
                              lambda j, i, tg, lt: (tg[i], j, 0))
    else:               # w [E, k, n]
        w_spec = pl.BlockSpec((None, k, block_n),
                              lambda j, i, tg, lt: (tg[i], 0, j))
    o_spec = pl.BlockSpec(
        (block_m, block_n), lambda j, i, tg, lt: (row_tile(j, i, tg, lt), j))
    return pl.pallas_call(
        functools.partial(_kernel, transpose_rhs=transpose_rhs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n // block_n, rows // block_m),
            in_specs=[x_spec, w_spec], out_specs=o_spec),
        out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="moe_gmm_t" if transpose_rhs else "moe_gmm",
    )(tile_group, live_tiles, x, w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _grouped(x, w, tile_group, live_tiles, block_m, block_n, interpret):
    return _gmm(x, w, tile_group, live_tiles, False, block_m, block_n,
                interpret)


def _grouped_fwd(x, w, tile_group, live_tiles, block_m, block_n, interpret):
    out = _grouped(x, w, tile_group, live_tiles, block_m, block_n, interpret)
    return out, (w, tile_group, live_tiles)


def _grouped_bwd(block_m, block_n, interpret, res, dy):
    w, tile_group, live_tiles = res
    dx = _gmm(dy, w, tile_group, live_tiles, True, block_m, block_n,
              interpret)
    # the experts are frozen: no product for their gradient is ever built
    return dx, None, None, None


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def reference_grouped_matmul(x, w, layout: GroupLayout, block_m: int):
    """Plain XLA: each row tile times its own expert's matrix, gathered."""
    tiles = x.shape[0] // block_m
    xt = x.reshape(tiles, block_m, x.shape[1])
    out = jnp.einsum("tmk,tkn->tmn", xt, w[layout.tile_group],
                     preferred_element_type=jnp.float32)
    return out.reshape(x.shape[0], w.shape[2]).astype(x.dtype)


def grouped_matmul(x: jax.Array, w: jax.Array, layout: GroupLayout,
                   block_m: int = BLOCK_M, block_n: int = BLOCK_N,
                   interpret: Optional[bool] = None) -> jax.Array:
    """``out[r] = x[r] @ w[expert of r's tile]``; x ``[P, K]`` in the
    layout's padded order, w ``[E, K, N]`` (frozen: it gets no gradient).

    ``block_m`` must be the layout's. ``interpret`` as in
    :func:`fedml_tpu.ops.flash_attention.flash_attention`.
    """
    mode = kernel_mode(interpret, off_tpu=REFERENCE)
    if mode == REFERENCE:
        return reference_grouped_matmul(
            x, jax.lax.stop_gradient(w), layout, block_m)
    return _grouped(x, w, layout.tile_group, layout.live_tiles, block_m,
                    block_n, mode == INTERPRET)
