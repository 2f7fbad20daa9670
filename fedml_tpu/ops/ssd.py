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
the chunk's end); a short scan over the chunk states gives the state each
chunk starts from, whose part of ``y`` is ``C_t h exp(sum_{s <= t} dt_s
a)``. The sequential loop above is ``models/llm/nemotron_h_reference.py``'s;
the two share no code.

The decays, their running sums and the chunk states are float32 whatever
the compute type; the four products (``C B^T``, the masked one, a chunk's
state, the state's part of ``y``) take operands of the compute type and
accumulate in float32. Plain XLA (batched products with 128 x 128 tiles
and fused elementwise passes), differentiated by JAX; the backward pass
recomputes what is inside from ``x``, ``dt``, ``B`` and ``C``
(``jax.checkpoint``), so a layer keeps none of the ``[H, Q, Q]`` arrays.
There is one form, so ``ops/dispatch.py`` has nothing to choose here.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from fedml_tpu.telemetry import get_tracer


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


@functools.partial(jax.jit, static_argnums=5)
def _ssd(x, dt, a, b, c, chunk):
    with jax.named_scope("ssd"):
        return jax.checkpoint(functools.partial(_chunked, chunk=chunk))(
            x, dt, a, b, c)


def ssd(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
        c: jax.Array, chunk: int = 128) -> jax.Array:
    """``y`` ``[B, T, H, P]`` of the recurrence above.

    ``x`` ``[B, T, H, P]``; ``dt`` ``[B, T, H]`` (positive: after its
    softplus); ``a`` ``[H]`` (negative); ``b``, ``c`` ``[B, T, G, N]`` with
    ``H`` a multiple of ``G``. A ``T`` that is not a whole number of chunks
    is padded with tokens of ``dt = 0`` (they decay nothing and add
    nothing) and cut again. ``D x`` (the skip) is the caller's.
    """
    bsz, t, heads, p = x.shape
    groups, n = b.shape[2:]
    if heads % groups:
        raise ValueError(f"ssd: {heads} heads are not a multiple of "
                         f"{groups} groups")
    chunk = min(chunk, t)
    pad = -t % chunk
    get_tracer().event(
        "ssd/plan", rows=bsz * t, heads=heads, head_dim=p, groups=groups,
        state=n, chunk=chunk, chunks=(t + pad) // chunk, form="chunked_xla",
        dtype=jnp.dtype(x.dtype).name)
    if pad:
        grow = lambda z: jnp.pad(z, [(0, 0), (0, pad)] + [(0, 0)] * (z.ndim - 2))
        x, dt, b, c = grow(x), grow(dt), grow(b), grow(c)
    return _ssd(x, dt, a, b, c, chunk)[:, :t]
