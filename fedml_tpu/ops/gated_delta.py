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
diagonal blocks up (:func:`_inverse`): ``log2 C`` levels of batched
products, in float32 at ``HIGHEST``, all on the MXU. Not the doubling
product ``(I - A)(I + A^2)(I + A^4)...``, exact in exact arithmetic: the
keys of a layer come out of a silu and lie in one orthant, the powers of
``A`` then grow by orders of magnitude before they cancel, and in float32
that product missed the inverse by 1e5 and more at ``C = 64`` (halving:
2e-7; ``tests/test_qwen3_next.py`` holds both the op and the inverse to
that).

Decays, their running sums, ``A``, ``T`` and the carried states are
float32 whatever the compute type; every other product takes operands of
the compute type (the inputs' dtype: ``T`` and the states are rounded to
it where they enter one) and accumulates in float32, as the chunked form
of ``ops/ssd.py`` does. One form, ``chunked_xla``: batched XLA
products over ``[B, chunks, H, C, C]`` arrays and a scan over chunk states,
differentiated by JAX; the backward computes the inside again
(``jax.checkpoint``: the residuals are the five inputs alone), so a layer
keeps none of those arrays. It runs inside ``jit(_gated_delta)`` under the
named scope ``gated_delta``: every operation of either direction carries
``.../jit(_gated_delta)/...`` in its ``op_name``, which is what
``gdn_scan_time_pct`` and ``gdn_roofline`` read. The sequential loop above
is ``models/llm/qwen3_next_reference.py``'s; no code is shared with it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
FORM = "chunked_xla"
INVERSE = "halving"


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


@functools.partial(jax.jit, static_argnums=(5,))
def _gated_delta(q, k, v, g, beta, chunk):
    with jax.named_scope("gated_delta"):
        return jax.checkpoint(functools.partial(_chunked, chunk=chunk))(
            q, k, v, g, beta)


def plan(batch: int, t: int, heads: int, key_dim: int, value_dim: int,
         chunk: int) -> dict:
    """What the op computes a call, for a ``gdn/plan`` event: its form,
    the inverse's form and the bytes of the chunk states it writes
    (``[B, chunks, H, Dk, Dv]`` float32)."""
    chunks = t // chunk
    return {"chunk": chunk, "chunks": chunks, "form": FORM,
            "inverse": INVERSE,
            "state_bytes": batch * chunks * heads * key_dim * value_dim * 4}


def gated_delta(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                beta: jax.Array, chunk: int = 64) -> jax.Array:
    """``o`` ``[B, T, H, Dv]`` of the recurrence above.

    ``q``, ``k`` ``[B, T, H, Dk]`` (a head each: a key head shared by
    several value heads is repeated by the caller; normalised and scaled
    as the layer wants them); ``v`` ``[B, T, H, Dv]``; ``g`` ``[B, T, H]``
    (log-decays, at most 0); ``beta`` ``[B, T, H]``. ``T`` must be a whole
    number of chunks: the caller pads (a token of ``g = beta = 0`` and
    zero ``k`` neither decays nor writes the state).
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
    return _gated_delta(q, k, v, g, beta, chunk)
