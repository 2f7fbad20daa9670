"""The gated delta rule of a Gated DeltaNet layer, in chunks.

For one sequence and one value head, keys and queries of ``Dk`` lanes,
values of ``Dv``, a decay ``g_t <= 0`` and a write strength ``beta_t`` in
(0, 1) a token::

    S' = exp(g_t) S_{t-1}                           S: [Dk, Dv], S_{-1} = 0
    delta_t = beta_t (v_t - S'^T k_t)               what the state mispredicts
    S_t = S' + k_t delta_t^T
    o_t = S_t^T q_t

Each update depends on the state itself (the state's own prediction is
subtracted), so no scalar decay turns the recurrence into one masked
product as in ``ops/ssd.py``. The chunked form (arXiv:2412.06464, the WY /
UT representation): the sequence is cut into chunks of ``C`` tokens;
within a chunk, with ``gamma_i = sum_{j <= i} g_j``::

    A_ij = beta_i exp(gamma_i - gamma_j) k_i . k_j   for i > j, else 0
    T = (I + A)^-1
    U = T (beta * V)          W = T (beta exp(gamma) * K)
    V_new = U - W S           (S: the state the chunk starts from)
    O = (exp(gamma) * Q) S + tril(Q K^T * exp(gamma_i - gamma_j)) V_new
    S <- exp(gamma_C) S + (exp(gamma_C - gamma) * K)^T V_new

``V_new`` is every token's ``delta``: the triangular system solves the
chunk's updates against one another. ``T`` is built by HALVING, from the
diagonal blocks up: the inverses of the ``h``-row diagonal blocks pair into
those of the ``2h``-row ones as ``[[X, 0], [L, Z]]^-1 = [[X^-1, 0],
[-Z^-1 L X^-1, Z^-1]]``, ``log2 C`` levels of products in float32 at
``HIGHEST``. Not the doubling product ``(I - A)(I + A^2)(I + A^4)...``,
exact in exact arithmetic: the keys of a layer come out of a silu and lie
in one orthant, the powers of ``A`` then grow by orders of magnitude before
they cancel, and in float32 that product missed the inverse by 1e5 and
more at ``C = 64``. Both forms below build ``T`` by halving, within 2e-7
of float64's (``tests/test_qwen3_next.py -k inverse`` holds each to 1e-6).

Decays, their running sums, ``A``, ``T`` and the carried states are
float32 whatever the compute type; every other product takes operands of
the compute type (the inputs' dtype: ``T`` and the states are rounded to
it where they enter one) and accumulates in float32. Two forms, chosen by
``ops/dispatch.py``:

- **kernel** (a TPU, or the interpreter): Pallas kernels each way. Grid
  ``(batch, head group, chunk)``, the chunks walked in order with the
  ``[Dk, Dv]`` float32 states of the group's value heads in VMEM scratch
  (``[heads a group x Dk, Dv]``, eight heads a step where the heads allow);
  a step reads one chunk's ``q``, ``k`` (``[C, heads a group x Dk]``,
  lane-dense blocks of the free view ``[B, T, H Dk]``), ``v``, and ``g``,
  ``beta`` (``[heads a group, C]`` float32, tokens on lanes) and writes
  ``o``, walking its heads in a ``fori_loop`` (a head's lanes at a
  dynamic, 128-aligned offset): unrolled, the three bodies took 11 s to
  trace and lower for a described v5e against 1.2 s for the chunked form,
  time every process spends in set-up; rolled, 1.3 to 2 s. The running
  sums, ``A``, ``T``, ``U``, ``W``, ``V_new``, the masked scores and the
  state update never leave VMEM. The running sum is
  a product with a 0/1 triangle at ``HIGHEST`` (float32 sums), and its
  transpose a product with the identity (the same bits: a token's gap to
  itself is exactly 0). The inverse (:func:`_vmem_inverse`): the diagonal
  blocks of 16 rows by forward substitution on the VPU, every block of
  the chunk at once (row ``r`` of each block is a strided load of
  ``[C / 16, C]``), then halving from 16 rows up written on whole ``[C,
  C]`` matrices: with ``T_h`` the block-diagonal inverse of the ``h``-row
  blocks and ``L_h`` the part of ``A`` in the lower-left quarters of the
  ``2h``-row blocks (a 0/1 mask of row and column bits), ``T_2h = T_h -
  T_h L_h T_h`` — the products outside the blocks add exact zeros, so a
  level computes the pairs' ``-Z^-1 L X^-1`` and nothing else, two ``[C,
  C]`` products at ``HIGHEST`` on the MXU. Against float64 on a layer's
  silu keys at beta 0.95 it misses by 0.8e-7 to 1.4e-7 at ``C`` 64 and 128
  (the batched halving below: 1.1e-7 to 2.0e-7). Blocks of 8 or 32 rows
  took longer on a TPU v5e (a layer-step at C = 128: 7.59 and 7.05 ms
  against 6.70), and so did the whole halving from 1 row at C = 64.
  The backward (a ``custom_vjp`` whose residuals are the five inputs
  alone) first writes each chunk's entry state and each head's ``T``
  (``gdn_states``: float32 ``[B, chunks, H x Dk, Dv]`` and ``[B, chunks,
  H x C, C]``, temporaries of the backward), then walks the chunks in
  reverse carrying the state's cotangent in VMEM (``gdn_bwd``): it builds
  ``A``, ``U``, ``W`` and ``V_new`` again from the inputs, the entry state
  and ``T`` (the inverse is the kernels' largest part: it is built twice
  a layer-step, not three times) and writes ``dq``, ``dk``, ``dv``,
  ``dg`` and ``dbeta``; ``dA = -T^T dT T^T`` at ``HIGHEST``, and a
  chunk's cotangent of the running sums is summed back to ``dg`` by one
  product with the triangle. On a TPU a shape the blocks cannot tile
  raises.
- **chunked_xla** (elsewhere, and the kernels' reference): batched XLA
  products over ``[B, chunks, H, C, C]`` arrays and a scan over chunk
  states, differentiated by JAX; the backward computes the inside again
  (``jax.checkpoint``: the residuals are the five inputs alone), so a layer
  keeps none of those arrays.

Both run inside ``jit(_gated_delta)`` under the named scope
``gated_delta``: every operation of either direction, the kernels'
``tpu_custom_call``s included, carries ``.../jit(_gated_delta)/...`` in
its ``op_name``, which is what ``gdn_scan_time_pct`` and ``gdn_roofline``
read. The sequential loop above is ``models/llm/qwen3_next_reference.py``'s;
no code is shared with it.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fedml_tpu.ops.dispatch import INTERPRET, REFERENCE, kernel_mode

_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))     # a @ b^T
_TN = (((0,), (0,)), ((), ()))     # a^T @ b
_VMEM_LIMIT = 64 * 1024 * 1024
_HEADS_A_STEP = 8                  # value heads a grid step, at most
_DIAGONAL_ROWS = 16                # rows of the inverse's blocks on the VPU


def _inverse(a):
    """``(I + A)^-1`` of strictly lower triangular ``A [..., C, C]``, from
    the diagonal blocks up: the inverses of the ``h``-row diagonal blocks
    pair into those of the ``2h``-row ones as ``[[X, 0], [L, Z]]^-1 =
    [[X^-1, 0], [-Z^-1 L X^-1, Z^-1]]``, every pair of a level in one
    batched product. ``C`` is padded to a power of two with zero rows and
    columns (an identity block below, which changes nothing above it)."""
    c, lead = a.shape[-1], a.shape[:-2]
    size = 1 << (c - 1).bit_length()
    if size != c:
        a = jnp.pad(a, [(0, 0)] * len(lead) + [(0, size - c)] * 2)
    inv = jnp.ones(lead + (size, 1, 1), _F32)     # [..., blocks, h, h]
    h = 1
    while h < size:
        n = size // (2 * h)
        # the lower left quarter L of each of A's diagonal blocks of 2h
        # rows, [..., n, h, h]: picked by a 0/1 mask and a sum (exact), so
        # neither direction gathers or scatters
        quarters = a.reshape(lead + (n, 2, h, n, 2, h))[..., 1, :, :, 0, :]
        low = jnp.sum(quarters * jnp.eye(n, dtype=_F32)[:, None, :, None],
                      axis=-2)
        pairs = inv.reshape(lead + (n, 2, h, h))
        x, z = pairs[..., 0, :, :], pairs[..., 1, :, :]
        y = -jnp.matmul(jnp.matmul(z, low, precision=_HIGHEST), x,
                        precision=_HIGHEST)
        inv = jnp.concatenate(
            [jnp.concatenate([x, jnp.zeros_like(x)], -1),
             jnp.concatenate([y, z], -1)], -2)
        h *= 2
    return inv[..., 0, :c, :c]


def _chunked(q, k, v, g, beta, chunk: int):
    bsz, t, heads, _ = q.shape
    dv = v.shape[-1]
    nc, dtype = t // chunk, q.dtype

    def dot(spec, a, b):
        return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                          preferred_element_type=_F32)

    # [B, c, H, C, ...]: chunks, then heads, the chunk's tokens next
    by_chunk = lambda z: z.reshape(bsz, nc, chunk, heads,
                                   *z.shape[3:]).swapaxes(2, 3)
    qc, kc, vc = by_chunk(q), by_chunk(k), by_chunk(v)
    bc = by_chunk(beta.astype(_F32))                            # [B,c,H,C]
    run = jnp.cumsum(by_chunk(g.astype(_F32)), axis=-1)         # gamma
    gap = run[..., :, None] - run[..., None, :]                 # [B,c,H,C,C]
    below = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    upto = jnp.tril(jnp.ones((chunk, chunk), bool))

    a = (bc[..., :, None] * jnp.exp(jnp.where(below, gap, -jnp.inf))
         * dot("bchid,bchjd->bchij", kc, kc))
    inv = _inverse(a)
    u = dot("bchij,bchjd->bchid", inv, bc[..., None] * vc)
    w = dot("bchij,bchjd->bchid", inv, (bc * jnp.exp(run))[..., None] * kc)
    scores = dot("bchid,bchjd->bchij", qc, kc) * jnp.exp(
        jnp.where(upto, gap, -jnp.inf))
    to_end = jnp.exp(run[..., -1:] - run)                       # [B,c,H,C]
    whole = jnp.exp(run[..., -1])                               # [B,c,H]

    def step(state, now):
        u_c, w_c, k_c, to_end_c, whole_c = now
        new = u_c - dot("bhid,bhde->bhie", w_c, state)
        state = whole_c[..., None, None] * state + dot(
            "bhid,bhie->bhde", to_end_c[..., None] * k_c, new)
        return state, (new, state)

    front = lambda z: jnp.moveaxis(z, 1, 0)
    back = lambda z: jnp.moveaxis(z, 0, 1)
    first = jnp.zeros((bsz, heads, k.shape[-1], dv), _F32)
    _, (new, after) = jax.lax.scan(
        step, first, tuple(front(z) for z in (u, w, kc, to_end, whole)))
    # the state each chunk starts from: zero, then what each chunk left
    before = jnp.concatenate([first[None], after[:-1]], axis=0)
    new, before = back(new), back(before)
    o = (dot("bchid,bchde->bchie", jnp.exp(run)[..., None] * qc, before)
         + dot("bchij,bchje->bchie", scores, new))
    return o.swapaxes(2, 3).reshape(bsz, t, heads, dv).astype(dtype)


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------
class _Geometry(NamedTuple):
    chunk: int    # C, tokens a grid step
    per: int      # value heads a grid step
    dk: int
    dv: int


def _geometry(chunk: int, heads: int, dk: int, dv: int) -> _Geometry:
    """Up to ``_HEADS_A_STEP`` heads a step, a number that divides the
    heads and fills whole 8 sublanes of ``g``'s block (or is every head)."""
    fits = [n for n in range(1, heads + 1)
            if heads % n == 0 and (n % 8 == 0 or n == heads)]
    small = [n for n in fits if n <= _HEADS_A_STEP]
    return _Geometry(chunk, max(small) if small else min(fits), dk, dv)


def _check_tiles(geo: _Geometry, heads: int):
    """The compiled kernels' blocks: a head's lanes in whole 128s,
    the chunk's tokens in whole 16 sublanes (a bfloat16 tile), the group's
    heads in whole 8 sublanes of ``g``'s block unless they are all."""
    needs = []
    for name, d in (("key", geo.dk), ("value", geo.dv)):
        if d % 128:
            needs.append(f"a {name} head of {d} is not whole 128 lanes")
    if geo.chunk % 16:
        needs.append(f"chunk {geo.chunk} is not whole 16 sublanes")
    if geo.per % 8 and geo.per != heads:
        needs.append(f"{geo.per} heads a step are not whole 8 sublanes")
    if needs:
        raise ValueError("gated_delta: the kernel cannot tile this shape: "
                         + "; ".join(needs))


def _dot(a, b, dims=_NN, precision=None):
    return jax.lax.dot_general(a, b, dims, precision=precision,
                               preferred_element_type=_F32)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _vmem_inverse(a, work):
    """``(I + A)^-1`` of one strictly lower triangular ``[C, C]`` ``A``;
    ``work`` is a ``[C, C]`` float32 VMEM scratch.

    The diagonal blocks of ``s`` rows (``_DIAGONAL_ROWS``, or the largest
    power of two below it that divides ``C``) by forward substitution on
    the VPU, every block at once: row ``r`` of each block (a strided load,
    ``[C / s, C]``) is ``e_r`` less its entries in the block's columns
    ``m < r`` times the rows ``m`` already solved. Then halving on whole
    matrices from ``s`` rows up, ``T_2h = T_h - T_h L_h T_h`` (module
    docstring), two products at ``HIGHEST`` a level. Any ``C`` that ``s``
    divides: every matrix is lower triangular, so the leading ``C`` rows
    and columns of a power-of-two size are computed alone."""
    c = a.shape[0]
    s = max(n for n in (1, 2, 4, 8, 16, 32, 64, 128)
            if n <= _DIAGONAL_ROWS and c % n == 0)
    nb = c // s
    col, first = _iota((nb, c), 1), _iota((nb, c), 0) * s
    at = [col == first + m for m in range(s)]   # column m of each block
    work[...] = a
    solved = []
    for r in range(s):
        rows = pl.ds(r, nb, stride=s)
        a_r = work[rows, :]                     # row r of every block
        x = at[r].astype(_F32)
        for m in range(r):
            entry = jnp.sum(jnp.where(at[m], a_r, 0.0), axis=1,
                            keepdims=True)      # A[bs + r, bs + m], [nb, 1]
            x = x - entry * solved[m]
        solved.append(x)
        work[rows, :] = x
    t = work[...]
    row, col = _iota((c, c), 0), _iota((c, c), 1)
    h = s
    while h < c:
        # row in the lower half of a 2h block, column in its upper half
        low = jnp.where(((row & h) != 0) & ((col & h) == 0)
                        & ((row ^ col) < 2 * h), a, 0.0)
        t = t - _dot(t, _dot(low, t, precision=_HIGHEST), precision=_HIGHEST)
        h *= 2
    return t


class _Chunk(NamedTuple):
    """A step's float32 decays for the group's heads: ``run`` [per, C] (the
    running sums ``gamma``, heads on sublanes), ``run_t`` [C, per] (the
    same, tokens on sublanes), ``beta_t`` [C, per]; ``lower`` the 0/1
    triangle ``[i, s]``: ``s <= i``."""

    run: jax.Array
    run_t: jax.Array
    beta_t: jax.Array
    lower: jax.Array


def _chunk(g_ref, b_ref, c: int) -> _Chunk:
    g = g_ref[...]                                             # [per, C]
    lower = (_iota((c, c), 1) <= _iota((c, c), 0)).astype(_F32)
    eye = (_iota((c, c), 1) == _iota((c, c), 0)).astype(_F32)
    # sums and transposes as products with 0/1 matrices, at float32 accuracy;
    # ``run_t`` is ``run`` transposed to the bit, so that a token's gap to
    # itself is exactly 0 however large its running sum
    run = _dot(g, lower, _NT, _HIGHEST)
    return _Chunk(run, _dot(eye, run, _NT, _HIGHEST),
                  _dot(eye, b_ref[...], _NT, _HIGHEST), lower)


def _pick_row(z, hd):
    """Row ``hd`` of ``z`` ``[per, n]`` as ``[1, n]`` (a masked sum: exact)."""
    return jnp.sum(jnp.where(_iota(z.shape, 0) == hd, z, 0.0), axis=0,
                   keepdims=True)


def _pick_col(z, hd):
    """Column ``hd`` of ``z`` ``[n, per]`` as ``[n, 1]``."""
    return jnp.sum(jnp.where(_iota(z.shape, 1) == hd, z, 0.0), axis=1,
                   keepdims=True)


class _Inside(NamedTuple):
    """One head's chunk, float32 unless named for the compute type."""

    beta: jax.Array     # [C, 1]
    e: jax.Array        # exp(gamma) [C, 1]
    to_end: jax.Array   # exp(gamma_C - gamma) [C, 1]
    e_end: jax.Array    # exp(gamma_C) [1, Dv]
    incl: jax.Array     # exp(gamma_i - gamma_j), j <= i, else 0 [C, C]
    strict: jax.Array   # the same, j < i
    kk: jax.Array       # K K^T
    a: jax.Array
    t: jax.Array
    tc: jax.Array       # T in the compute type
    bv: jax.Array       # beta V, compute type
    bk32: jax.Array     # beta exp(gamma) K
    bk: jax.Array       # the same, compute type
    w: jax.Array
    vn: jax.Array       # V_new = U - W S


def _inside(ch: _Chunk, hd, k, v, s, inverse) -> _Inside:
    """Head ``hd``'s ``A``, ``T = inverse(A)``, ``U``, ``W`` and ``V_new``
    from its chunk of ``k``, ``v`` (compute type) and the float32 state
    ``s`` the chunk starts from."""
    c, cdt = k.shape[0], k.dtype
    r_row, r_col = _pick_row(ch.run, hd), _pick_col(ch.run_t, hd)
    r_end = r_row[:, c - 1:c]
    beta = _pick_col(ch.beta_t, hd)
    incl = jnp.exp(jnp.where(ch.lower > 0, r_col - r_row, -jnp.inf))
    strict = jnp.where(_iota((c, c), 1) < _iota((c, c), 0), incl, 0.0)
    kk = _dot(k, k, _NT)
    a = beta * strict * kk
    t = inverse(a)
    e = jnp.exp(r_col)
    bk32 = (beta * e) * k.astype(_F32)
    tc, bv, bk = (t.astype(cdt), (beta * v.astype(_F32)).astype(cdt),
                  bk32.astype(cdt))
    w = _dot(tc, bk)
    vn = _dot(tc, bv) - _dot(w.astype(cdt), s.astype(cdt))
    # [1, Dv]: Mosaic broadcasts a [1, 1] in lanes or in sublanes, not both
    e_end = jnp.exp(r_end + jnp.zeros((1, v.shape[1]), _F32))
    return _Inside(beta, e, jnp.exp(r_end - r_col), e_end, incl,
                   strict, kk, a, t, tc, bv, bk32, bk, w, vn)


def _advance(x: _Inside, k, s):
    """The state the chunk leaves: ``s`` decayed to the chunk's end plus
    ``(exp(gamma_C - gamma) K)^T V_new``."""
    cdt = k.dtype
    return x.e_end * s + _dot((x.to_end * k.astype(_F32)).astype(cdt),
                              x.vn.astype(cdt), _TN)


def _heads(geo: _Geometry, body, carry=None):
    """``body(hd, key lanes, value lanes, carry)`` for the step's heads one
    after another (a loop, so that each kernel's body is traced and lowered
    once, not once a head); a head's lanes are whole 128s."""
    def one(hd, carry):
        return body(hd, pl.ds(pl.multiple_of(hd * geo.dk, geo.dk), geo.dk),
                    pl.ds(pl.multiple_of(hd * geo.dv, geo.dv), geo.dv), carry)
    return jax.lax.fori_loop(0, geo.per, one, carry)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, state, work, *,
                geo: _Geometry):
    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    ch = _chunk(g_ref, b_ref, geo.chunk)

    def head(hd, kl, vl, carry):
        q, k, s = q_ref[:, kl], k_ref[:, kl], state[kl, :]
        cdt = q.dtype
        x = _inside(ch, hd, k, v_ref[:, vl], s,
                    lambda a: _vmem_inverse(a, work))
        scores = (_dot(q, k, _NT) * x.incl).astype(cdt)
        o = (_dot((x.e * q.astype(_F32)).astype(cdt), s.astype(cdt))
             + _dot(scores, x.vn.astype(cdt)))
        o_ref[:, vl] = o.astype(o_ref.dtype)
        state[kl, :] = _advance(x, k, s)
        return carry

    _heads(geo, head)


def _states_kernel(k_ref, v_ref, g_ref, b_ref, h_ref, t_ref, state, work, *,
                   geo: _Geometry):
    """The state each chunk starts from, and each head's ``T`` (into
    ``t_ref``, ``[heads a step x C, C]``), for the backward."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    h_ref[...] = state[...]                  # the state the chunk starts from
    c = geo.chunk
    ch = _chunk(g_ref, b_ref, c)

    def head(hd, kl, vl, carry):
        k, s = k_ref[:, kl], state[kl, :]
        x = _inside(ch, hd, k, v_ref[:, vl], s,
                    lambda a: _vmem_inverse(a, work))
        t_ref[pl.ds(pl.multiple_of(hd * c, c), c), :] = x.t
        state[kl, :] = _advance(x, k, s)
        return carry

    _heads(geo, head)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, do_ref, h_ref, t_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, db_ref, dstate, *,
                geo: _Geometry):
    """One chunk, walked last to first. ``dstate`` holds the cotangent of
    the state the chunk leaves (zero after the last), ``h_ref`` the state
    it starts from, ``t_ref`` each head's ``T`` as ``gdn_states`` built
    it. Per head, with ``Qe = exp(gamma) Q``, ``Kt =
    exp(gamma_C - gamma) K`` and ``P`` the masked scores::

        O = Qe S + P V_new          S_end = exp(gamma_C) S + Kt^T V_new
        V_new = T (beta V) - T (beta exp(gamma) K) S

    A cotangent of the running sums is gathered per token, as a column
    (``[C, per]``, where the token is a row ``i``) or as a row (``[per,
    C]``, where it is a column ``j``), and summed back to ``g`` by one
    product with the triangle."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    c = geo.chunk
    ch = _chunk(g_ref, b_ref, c)
    head_lane, head_row = _iota((1, geo.per), 1), _iota((geo.per, 1), 0)
    last = _iota((c, 1), 0) == c - 1
    rows = lambda z: jnp.sum(z, axis=1, keepdims=True)
    cols = lambda z: jnp.sum(z, axis=0, keepdims=True)

    def head(hd, kl, vl, carry):
        d_run_t, d_run, d_beta_t = carry
        q, k, v, do = q_ref[:, kl], k_ref[:, kl], v_ref[:, vl], do_ref[:, vl]
        cdt = q.dtype
        q32, k32 = q.astype(_F32), k.astype(_F32)
        s, ds_end = h_ref[kl, :], dstate[kl, :]
        t_rows = pl.ds(pl.multiple_of(hd * c, c), c)
        x = _inside(ch, hd, k, v, s, lambda a: t_ref[t_rows, :])
        sc, vnc, dsc = s.astype(cdt), x.vn.astype(cdt), ds_end.astype(cdt)
        qe32, kt32 = x.e * q32, x.to_end * k32
        p = _dot(q, k, _NT) * x.incl
        # O and S_end
        d_vn = _dot(p.astype(cdt), do, _TN) + _dot(kt32.astype(cdt), dsc)
        d_p = _dot(do, vnc, _NT)
        d_qe = _dot(do, sc, _NT)
        d_kt = _dot(vnc, dsc, _NT)
        ds = _dot(qe32.astype(cdt), do, _TN) + x.e_end * ds_end
        # V_new = U - W S, U = T (beta V), W = T (beta exp(gamma) K)
        d_vnc = d_vn.astype(cdt)
        d_wc = (-_dot(d_vnc, sc, _NT)).astype(cdt)
        ds = ds - _dot(x.w.astype(cdt), d_vnc, _TN)
        d_t = _dot(d_vnc, x.bv, _NT) + _dot(d_wc, x.bk, _NT)
        d_bv = _dot(x.tc, d_vnc, _TN)
        d_bk = _dot(x.tc, d_wc, _TN)
        # T = (I + A)^-1, A = beta exp(gamma_i - gamma_j) K K^T below
        d_a = -_dot(_dot(x.t, d_t, _TN, _HIGHEST), x.t, _NT, _HIGHEST)
        d_kk = (d_a * x.beta * x.strict).astype(cdt)
        d_pm = (d_p * x.incl).astype(cdt)
        dq_ref[:, kl] = (x.e * d_qe + _dot(d_pm, k)).astype(dq_ref.dtype)
        dk_ref[:, kl] = (x.to_end * d_kt + x.beta * x.e * d_bk
                         + _dot(d_kk, k) + _dot(d_kk, k, _TN)
                         + _dot(d_pm, q, _TN)).astype(dk_ref.dtype)
        dv_ref[:, vl] = (x.beta * d_bv).astype(dv_ref.dtype)
        dstate[kl, :] = ds
        # beta and the running sums
        beta_col = (rows(d_bv * v.astype(_F32)) + rows(d_bk * x.e * k32)
                    + rows(d_a * x.strict * x.kk))
        g_p, g_a = d_p * p, d_a * x.a
        kt_dkt = rows(d_kt * kt32)
        at_end = cols(kt_dkt) + jnp.sum(x.e_end * s * ds_end, keepdims=True)
        col = (rows(g_p) + rows(g_a) + rows(d_qe * qe32) - kt_dkt
               + rows(d_bk * x.bk32) + jnp.where(last, at_end, 0.0))
        return (jnp.where(head_lane == hd, col, d_run_t),
                jnp.where(head_row == hd, -cols(g_p) - cols(g_a), d_run),
                jnp.where(head_lane == hd, beta_col, d_beta_t))

    zeros_t = jnp.zeros((c, geo.per), _F32)
    d_run_t, d_run, d_beta_t = _heads(
        geo, head, (zeros_t, jnp.zeros((geo.per, c), _F32), zeros_t))
    # the cotangent of g at token s is that of every gamma_i with i >= s
    eye = (_iota((c, c), 1) == _iota((c, c), 0)).astype(_F32)
    dg_ref[...] = (_dot(d_run_t, ch.lower, _TN, _HIGHEST)
                   + _dot(d_run, ch.lower, _NN, _HIGHEST))
    db_ref[...] = _dot(d_beta_t, eye, _TN, _HIGHEST)


def _specs(geo: _Geometry, nc: int, reverse: bool):
    """BlockSpecs of a grid ``(batch, group, chunk)``: ``q``-like ``[B, T,
    H Dk]``, ``v``-like ``[B, T, H Dv]``, ``g``-like ``[B, chunks, H, C]``,
    states ``[B, chunks, H Dk, Dv]``."""
    at = (lambda ci: nc - 1 - ci) if reverse else (lambda ci: ci)
    return dict(
        q=pl.BlockSpec((None, geo.chunk, geo.per * geo.dk),
                       lambda bi, gi, ci: (bi, at(ci), gi)),
        v=pl.BlockSpec((None, geo.chunk, geo.per * geo.dv),
                       lambda bi, gi, ci: (bi, at(ci), gi)),
        g=pl.BlockSpec((None, None, geo.per, geo.chunk),
                       lambda bi, gi, ci: (bi, at(ci), gi, 0)),
        state=pl.BlockSpec((None, None, geo.per * geo.dk, geo.dv),
                           lambda bi, gi, ci: (bi, at(ci), gi, 0)),
        t=pl.BlockSpec((None, None, geo.per * geo.chunk, geo.chunk),
                       lambda bi, gi, ci: (bi, at(ci), gi, 0)))


def _call(kernel, name, geo: _Geometry, grid, in_specs, out_specs,
          out_shape, interpret, work=False):
    """A kernel with the state (or its cotangent) in VMEM scratch and,
    where it builds ``T`` itself, a ``[C, C]`` float32 scratch for the
    inverse (``work``)."""
    scratch = [pltpu.VMEM((geo.per * geo.dk, geo.dv), _F32)]
    if work:
        scratch.append(pltpu.VMEM((geo.chunk, geo.chunk), _F32))
    return pl.pallas_call(
        functools.partial(kernel, geo=geo),
        grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=scratch,
        # the chunk axis carries the state (or its cotangent)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name=name)


def _flat(q, k, v, g, beta, chunk):
    """The kernels' views: ``q``, ``k`` [B, T, H Dk], ``v`` [B, T, H Dv],
    ``g``, ``beta`` [B, chunks, H, C] float32."""
    bsz, t, heads, _ = q.shape
    by_chunk = lambda z: z.astype(_F32).reshape(
        bsz, t // chunk, chunk, heads).swapaxes(2, 3)
    return (q.reshape(bsz, t, -1), k.reshape(bsz, t, -1),
            v.reshape(bsz, t, -1), by_chunk(g), by_chunk(beta))


def _unchunk(z, like):
    """``[B, chunks, H, C]`` back to ``like``'s ``[B, T, H]``."""
    return z.swapaxes(2, 3).reshape(like.shape).astype(like.dtype)


# One signature a layer: under ``jit`` each body is traced and lowered once
# for all the layers that share it, not once a layer.
@functools.partial(jax.jit, static_argnums=(5, 6))
def _fwd(q, k, v, g, beta, chunk, interpret):
    bsz, t, heads, dk = q.shape
    geo, nc = _geometry(chunk, heads, dk, v.shape[-1]), t // chunk
    sp = _specs(geo, nc, reverse=False)
    views = _flat(q, k, v, g, beta, chunk)
    o = _call(_fwd_kernel, "gdn_fwd", geo, (bsz, heads // geo.per, nc),
              [sp["q"], sp["q"], sp["v"], sp["g"], sp["g"]], sp["v"],
              jax.ShapeDtypeStruct(views[2].shape, v.dtype), interpret,
              work=True)(*views)
    return o.reshape(v.shape)


@functools.partial(jax.jit, static_argnums=(5, 6))
def _bwd(q, k, v, g, beta, chunk, interpret, do):
    bsz, t, heads, dk = q.shape
    dv = v.shape[-1]
    geo, nc = _geometry(chunk, heads, dk, dv), t // chunk
    grid = (bsz, heads // geo.per, nc)
    views = _flat(q, k, v, g, beta, chunk)
    fwd, rev = _specs(geo, nc, reverse=False), _specs(geo, nc, reverse=True)
    states, inverses = _call(
        _states_kernel, "gdn_states", geo, grid,
        [fwd["q"], fwd["v"], fwd["g"], fwd["g"]], [fwd["state"], fwd["t"]],
        [jax.ShapeDtypeStruct((bsz, nc, heads * dk, dv), _F32),
         jax.ShapeDtypeStruct((bsz, nc, heads * chunk, chunk), _F32)],
        interpret, work=True)(*views[1:])
    dq, dk_, dv_, dg, db = _call(
        _bwd_kernel, "gdn_bwd", geo, grid,
        [rev["q"], rev["q"], rev["v"], rev["g"], rev["g"], rev["v"],
         rev["state"], rev["t"]],
        [rev["q"], rev["q"], rev["v"], rev["g"], rev["g"]],
        [jax.ShapeDtypeStruct(views[0].shape, q.dtype),
         jax.ShapeDtypeStruct(views[1].shape, k.dtype),
         jax.ShapeDtypeStruct(views[2].shape, v.dtype),
         jax.ShapeDtypeStruct(views[3].shape, _F32),
         jax.ShapeDtypeStruct(views[4].shape, _F32)], interpret)(
        *views, do.reshape(views[2].shape), states, inverses)
    return (dq.reshape(q.shape), dk_.reshape(k.shape), dv_.reshape(v.shape),
            _unchunk(dg, g), _unchunk(db, beta))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _kernel(q, k, v, g, beta, chunk, interpret):
    return _fwd(q, k, v, g, beta, chunk, interpret)


def _kernel_fwd(q, k, v, g, beta, chunk, interpret):
    # the inputs alone: what jax.checkpoint keeps of the chunked form
    return _fwd(q, k, v, g, beta, chunk, interpret), (q, k, v, g, beta)


def _kernel_bwd(chunk, interpret, res, do):
    return _bwd(*res, chunk, interpret, do)


_kernel.defvjp(_kernel_fwd, _kernel_bwd)


@functools.partial(jax.jit, static_argnums=(5, 6))
def _gated_delta(q, k, v, g, beta, chunk, form):
    with jax.named_scope("gated_delta"):
        if form == REFERENCE:
            return jax.checkpoint(functools.partial(_chunked, chunk=chunk))(
                q, k, v, g, beta)
        return _kernel(q, k, v, g, beta, chunk, form == INTERPRET)


def plan(batch: int, t: int, heads: int, key_dim: int, value_dim: int,
         chunk: int) -> dict:
    """What the op computes a call, for a ``gdn/plan`` event: its form
    (``kernel`` or ``chunked_xla``, as :func:`gated_delta` picks it for
    model code), how the inverse is built, the value heads a step and
    ``state_bytes``: the VMEM state of a step's heads (the kernel) or the
    chunk states written (``[B, chunks, H, Dk, Dv]`` float32, the chunked
    form)."""
    chunks = t // chunk
    if kernel_mode(None, off_tpu=REFERENCE) == REFERENCE:
        form, per, inverse = "chunked_xla", heads, "halving"
        state_bytes = batch * chunks * heads * key_dim * value_dim * 4
    else:
        form, inverse = "kernel", "substitution_halving"
        per = _geometry(chunk, heads, key_dim, value_dim).per
        state_bytes = per * key_dim * value_dim * 4
    return {"chunk": chunk, "chunks": chunks, "form": form,
            "heads_per_step": per, "inverse": inverse,
            "state_bytes": state_bytes}


def gated_delta(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                beta: jax.Array, chunk: int = 64,
                interpret: Optional[bool] = None) -> jax.Array:
    """``o`` ``[B, T, H, Dv]`` of the recurrence above.

    ``q``, ``k`` ``[B, T, H, Dk]`` (a head each: a key head shared by
    several value heads is repeated by the caller; normalised and scaled
    as the layer wants them); ``v`` ``[B, T, H, Dv]``; ``g`` ``[B, T, H]``
    (log-decays, at most 0); ``beta`` ``[B, T, H]``. ``T`` must be a whole
    number of chunks: the caller pads (a token of ``g = beta = 0`` and
    zero ``k`` neither decays nor writes the state).

    ``interpret=None`` (model code): the kernels on a TPU, the chunked XLA
    form elsewhere; ``interpret=True`` runs the kernels under the Pallas
    interpreter; ``interpret=False`` always emits the compiled kernels —
    see :mod:`fedml_tpu.ops.dispatch`.
    """
    bsz, t, heads, dk = q.shape
    if k.shape != q.shape or v.shape[:3] != (bsz, t, heads) \
            or g.shape != (bsz, t, heads) or beta.shape != g.shape:
        raise ValueError(
            f"gated_delta: shapes q {q.shape}, k {k.shape}, v {v.shape}, "
            f"g {g.shape}, beta {beta.shape} do not agree")
    if t % chunk:
        raise ValueError(f"gated_delta: T = {t} is not a whole number of "
                         f"chunks of {chunk}")
    mode = kernel_mode(interpret, off_tpu=REFERENCE)
    if mode not in (REFERENCE, INTERPRET):
        _check_tiles(_geometry(chunk, heads, dk, v.shape[-1]), heads)
    return _gated_delta(q, k, v, g, beta, chunk, mode)
