"""Blockwise robust-aggregation math for payloads larger than HBM.

SURVEY §7 hard part (e): one fp32 vector of a 6.76B-param model is
27 GB, so the N×D stacked update matrix the plain defenses build
(``stack_updates``) can never be device-resident on a 16 GB chip for
full-parameter LLM federation. Reference counterparts
(``core/security/defense/krum_defense.py``,
``coordinate_wise_median_defense.py``, ``RFA_defense.py``) sidestep the
question by running per-pair numpy loops on the host — correct but
orders of magnitude slower and still RAM-bound.

Here every robust aggregator decomposes into per-block device programs
over ``[N, C]`` slices of the virtual N×D matrix, streamed in flattened
leaf order with a fixed block width (one compiled program per op):

- krum / pairwise distances — gram accumulation ``G += X_b @ X_bᵀ``;
  distances follow from ``G`` alone, so device memory is N×C + N×N;
- coordinate-wise median / trimmed mean — per-coordinate, embarrassingly
  blockwise;
- geometric median — smoothed Weiszfeld; each iteration is one
  distance-accumulation pass plus one weighted-reduction pass.

Client payloads stay in host RAM (they arrive from the federation
transport as host arrays anyway); the device holds at most one block.
Blocks enter via an iterator so benchmarks can synthesize them on-device
(GB-scale host→device pushes would measure the host link, not the
defense).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Iterable, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Pytree = Any

# 1<<25 elems × 8 clients × 4 B = 1 GB device-resident per block at N=8
DEFAULT_BLOCK_ELEMS = 1 << 25


def flatten_clients(trees: Sequence[Pytree]) -> List[List[np.ndarray]]:
    """Per-client flattened leaf lists (host views where possible)."""
    return [
        [np.asarray(leaf).reshape(-1) for leaf in jax.tree.leaves(t)]
        for t in trees
    ]


def iter_blocks(
    flat_clients: List[List[np.ndarray]],
    block_elems: int = DEFAULT_BLOCK_ELEMS,
) -> Iterable[Tuple[np.ndarray, int]]:
    """Yield ``(block [N, block_elems] fp32, valid_width)`` slices of the
    virtual concatenated N×D matrix. The final block is zero-padded to the
    fixed width so every block hits the same compiled program."""
    n = len(flat_clients)
    n_leaves = len(flat_clients[0])
    block = np.zeros((n, block_elems), np.float32)
    fill = 0
    for li in range(n_leaves):
        size = flat_clients[0][li].size
        off = 0
        while off < size:
            take = min(block_elems - fill, size - off)
            for ci in range(n):
                block[ci, fill : fill + take] = flat_clients[ci][li][
                    off : off + take
                ]
            fill += take
            off += take
            if fill == block_elems:
                yield block, fill
                block = np.zeros((n, block_elems), np.float32)
                fill = 0
    if fill:
        block[:, fill:] = 0.0
        yield block, fill


@jax.jit
def _gram_update(g: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    return g + x @ x.T


def pairwise_sq_dists_blockwise(
    blocks: Iterable[Tuple[Any, int]], n: int
) -> np.ndarray:
    """N×N squared L2 distances without ever materializing N×D.

    Zero padding contributes nothing to the gram, so padded tails are
    harmless. d_ij = g_ii + g_jj - 2 g_ij, clamped at 0.
    """
    g = jnp.zeros((n, n), jnp.float32)
    for x, _ in blocks:
        g = _gram_update(g, jnp.asarray(x, jnp.float32))
    g = np.asarray(g)
    sq = np.diag(g)
    d = sq[:, None] + sq[None, :] - 2.0 * g
    return np.maximum(d, 0.0)


@functools.partial(jax.jit, static_argnames=("k",))
def _trimmed_mean_block(x: jnp.ndarray, k: int) -> jnp.ndarray:
    xs = jnp.sort(x, axis=0)
    kept = jax.lax.slice_in_dim(xs, k, x.shape[0] - k, axis=0)
    return jnp.mean(kept, axis=0)


@jax.jit
def _median_block(x: jnp.ndarray) -> jnp.ndarray:
    return jnp.median(x, axis=0)


@jax.jit
def _weighted_sum_block(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    return jnp.einsum("n,nc->c", w, x)


@jax.jit
def _sqdist_to_z_block(x: jnp.ndarray, z: jnp.ndarray) -> jnp.ndarray:
    d = x - z[None, :]
    return jnp.sum(d * d, axis=1)


def coordinate_reduce_blockwise(
    trees: Sequence[Pytree],
    reduce_block: Callable[[jnp.ndarray], jnp.ndarray],
    block_elems: int = DEFAULT_BLOCK_ELEMS,
) -> Pytree:
    """Apply a per-coordinate reduction (median, trimmed mean, …) over the
    client axis, block by block; returns a tree like one client's."""
    flat = flatten_clients(trees)
    total = sum(a.size for a in flat[0])
    out = np.empty((total,), np.float32)
    pos = 0
    for x, width in iter_blocks(flat, block_elems):
        r = np.asarray(reduce_block(jnp.asarray(x)))
        out[pos : pos + width] = r[:width]
        pos += width
    return _unflatten_like(out, trees[0])


def trimmed_mean_blockwise(trees, k: int,
                           block_elems: int = DEFAULT_BLOCK_ELEMS) -> Pytree:
    return coordinate_reduce_blockwise(
        trees, lambda x: _trimmed_mean_block(x, k), block_elems)


def coordinate_median_blockwise(
        trees, block_elems: int = DEFAULT_BLOCK_ELEMS) -> Pytree:
    return coordinate_reduce_blockwise(trees, _median_block, block_elems)


def geometric_median_blockwise(
    trees: Sequence[Pytree],
    weights: Sequence[float],
    iters: int = 10,
    eps: float = 1e-8,
    block_elems: int = DEFAULT_BLOCK_ELEMS,
) -> Pytree:
    """Smoothed Weiszfeld over blocks: per iteration, one full pass
    accumulates every client's squared distance to the current estimate,
    then one pass rebuilds the estimate from the reweighted average."""
    flat = flatten_clients(trees)
    n = len(flat)
    total = sum(a.size for a in flat[0])
    w = np.asarray(weights, np.float64)
    w = w / w.sum()

    # z₀ = weighted mean, built blockwise
    z = np.empty((total,), np.float32)
    wj = jnp.asarray(w, jnp.float32)
    pos = 0
    for x, width in iter_blocks(flat, block_elems):
        z[pos : pos + width] = np.asarray(
            _weighted_sum_block(jnp.asarray(x), wj))[:width]
        pos += width

    for _ in range(iters):
        sqd = np.zeros((n,), np.float64)
        pos = 0
        for x, width in iter_blocks(flat, block_elems):
            zb = jnp.asarray(z[pos : pos + block_elems]
                             if width == block_elems
                             else np.concatenate([
                                 z[pos : pos + width],
                                 np.zeros(block_elems - width, np.float32)]))
            sqd += np.asarray(_sqdist_to_z_block(jnp.asarray(x), zb),
                              np.float64)
            pos += width
        alpha = w / np.sqrt(sqd + eps)
        alpha = alpha / alpha.sum()
        aj = jnp.asarray(alpha, jnp.float32)
        pos = 0
        for x, width in iter_blocks(flat, block_elems):
            z[pos : pos + width] = np.asarray(
                _weighted_sum_block(jnp.asarray(x), aj))[:width]
            pos += width
    return _unflatten_like(z, trees[0])


def _unflatten_like(vec: np.ndarray, template: Pytree) -> Pytree:
    leaves, treedef = jax.tree.flatten(template)
    out, pos = [], 0
    for leaf in leaves:
        size = int(np.prod(np.shape(leaf)) or 1)
        out.append(
            np.asarray(vec[pos : pos + size], np.float32)
            .reshape(np.shape(leaf))
            .astype(np.asarray(leaf).dtype)
        )
        pos += size
    return jax.tree.unflatten(treedef, out)


def stacked_bytes(raw_client_grad_list: List[Tuple[int, Pytree]]) -> int:
    """fp32 bytes the dense N×D stack would occupy."""
    n = len(raw_client_grad_list)
    d = sum(int(np.prod(np.shape(x)) or 1)
            for x in jax.tree.leaves(raw_client_grad_list[0][1]))
    return 4 * n * d


def should_go_blockwise(raw_client_grad_list, args: Any,
                        default_budget: int = 4 << 30) -> bool:
    """True when the dense stack would exceed the device budget
    (``defense_stack_budget_bytes``, default 4 GB — the stack shares HBM
    with the model, gram workspace, and XLA scratch)."""
    budget = int(getattr(args, "defense_stack_budget_bytes", 0)
                 or default_budget)
    return stacked_bytes(raw_client_grad_list) > budget
