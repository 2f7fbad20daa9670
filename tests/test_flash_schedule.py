"""The flash kernels' causal schedule (``ops/flash_attention.py``): the plan
checked by brute force against the score square, the kernels it builds
checked against ``reference_attention`` in float32 under the interpreter,
and the ``kernel/flash/plan`` event a traced kernel leaves."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.ops.flash_attention import (
    MASKED,
    SKIPPED,
    UNMASKED,
    _block,
    _first_q,
    _last_kv,
    causal_plan,
    flash_attention,
    reference_attention,
)
from fedml_tpu.telemetry import get_tracer

# (T, S, block_q, block_k, causal); the two cells' calls come first
PLANS = [
    (4096, 4096, None, None, True),    # smollm2-1.7b.round-long
    (512, 512, None, None, True),      # yi-6b.round-short
    (4096, 4096, 512, 1024, True),     # the former blocks: two diagonal phases
    (4096, 4096, 1024, 512, True),
    (1024, 1024, 256, 384, True),      # block_k no multiple of block_q
    (512, 512, 128, 256, True),
    (384, 384, 128, 128, True),
    (100, 100, 32, 32, True),          # ragged, no sub-tiles
    (160, 160, 64, 64, True),
    (320, 320, 128, 256, True),        # ragged and sub-tiled
    (2176, 2176, None, None, True),    # a multiple of 128, not of the block
    (512, 512, None, None, False),
    (384, 640, 128, 256, False),
    (100, 160, 32, 64, False),
]


def _blocks(t, s, block_q, block_k):
    return block_q or _block(t, 64, 512), block_k or _block(s, 64, 1024)


@pytest.mark.parametrize("t,s,block_q,block_k,causal", PLANS)
def test_plan_covers_the_needed_scores_exactly_once(t, s, block_q, block_k,
                                                    causal):
    block_q, block_k = _blocks(t, s, block_q, block_k)
    plan = causal_plan(t, s, block_q, block_k, causal)
    rows, cols = np.ogrid[:t, :s]
    needed = (rows >= cols) if causal else np.ones((t, s), bool)
    computed = np.zeros((t, s), np.int8)
    everything = np.zeros((t, s), np.int8)
    area = 0
    for r0, r1, c0, c1, kind in plan["tiles"]:
        inside = needed[r0:r1, c0:c1]  # numpy clips a tile past the edge
        everything[r0:r1, c0:c1] += 1
        if kind == SKIPPED:
            assert not inside.any(), (r0, r1, c0, c1)
            continue
        computed[r0:r1, c0:c1] += 1
        area += (r1 - r0) * (c1 - c0)
        if kind == UNMASKED:  # whole, inside the square, nothing to hide
            assert r1 <= t and c1 <= s and inside.all(), (r0, r1, c0, c1)
        else:
            assert kind == MASKED
    assert (everything == 1).all()
    assert (computed[needed] == 1).all()
    assert plan["visited"] == plan["masked"] + plan["unmasked"]
    grid = -(-t // plan["block_q"]) * -(-s // plan["block_k"])
    assert plan["visited"] + plan["skipped"] == grid
    assert plan["area_ratio"] == pytest.approx(area / needed.sum())
    if not causal:
        assert plan["skipped"] == 0
    if (t, s, causal) == (4096, 4096, True):
        assert plan["area_ratio"] <= 1.10
    if (t, s, causal) == (512, 512, True):
        assert plan["area_ratio"] <= 1.30


@pytest.mark.parametrize("t,s,block_q,block_k,causal", PLANS)
def test_index_maps_stop_where_the_plan_skips(t, s, block_q, block_k, causal):
    """The clamps of the BlockSpecs and the plan's skipped pairs agree."""
    plan = causal_plan(t, s, *_blocks(t, s, block_q, block_k), causal)
    g = plan["geometry"]
    kinds = np.array(plan["pairs"]).reshape(g.q_steps, g.kv_steps)
    for qi in range(g.q_steps):
        visited = np.flatnonzero(kinds[qi] != SKIPPED)
        assert list(visited) == list(range(int(_last_kv(g, qi)) + 1))
    for ki in range(g.kv_steps):
        visited = np.flatnonzero(kinds[:, ki] != SKIPPED)
        assert list(visited) == list(range(_first_q(g, ki), g.q_steps))


def test_plan_counts_of_the_long_cell():
    assert {k: causal_plan(4096, 4096, 512, 1024, True)[k]
            for k in ("visited", "masked", "unmasked", "skipped")} == {
        "visited": 20, "masked": 8, "unmasked": 12, "skipped": 12}
    assert {k: causal_plan(4096, 4096, 2048, 2048, True)[k]
            for k in ("visited", "masked", "unmasked", "skipped")} == {
        "visited": 3, "masked": 2, "unmasked": 1, "skipped": 1}


@pytest.mark.parametrize("t,s", [(128, 256), (256, 128)])
def test_causal_needs_equal_lengths(t, s):
    with pytest.raises(ValueError, match="T == S"):
        causal_plan(t, s, 64, 64, True)
    q = jnp.zeros((1, 2, t, 16))
    kv = jnp.zeros((1, 2, s, 16))
    with pytest.raises(ValueError, match="T == S"):
        flash_attention(q, kv, kv, causal=True, interpret=True)
    # not causal: any lengths
    assert flash_attention(q, kv, kv, causal=False,
                           interpret=True).shape == q.shape


def _qkv(t, heads, kv_heads, d, dtype, seed=0):
    key = jax.random.key(seed)
    shapes = [(1, heads, t, d), (1, kv_heads, t, d), (1, kv_heads, t, d),
              (1, heads, t, d)]
    q, k, v, w = (jax.random.normal(jax.random.fold_in(key, i), shape)
                  for i, shape in enumerate(shapes))
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), w


# T spans three q blocks or more, so a call holds blocks above, on and below
# the diagonal; 100 and 160 are ragged, 320 ragged with sub-tiles. Heads of
# 32 throughout, heads of 256 (``glm4_moe_lite``'s: as many key-value
# heads as query heads) whole and ragged, and heads of 256 with eight query
# heads a key-value head (``qwen3_next``'s gated attention)
PARITY = [
    # t, heads, kv_heads, block_q, block_k, d
    (512, 2, 2, 128, 256, 32),
    (512, 4, 1, 128, 256, 32),
    (768, 2, 2, 256, 256, 32),
    (512, 4, 1, 256, 128, 32),
    (100, 2, 2, 32, 32, 32),
    (160, 4, 1, 32, 64, 32),
    (320, 2, 2, 128, 128, 32),
    (512, 4, 4, 128, 256, 256),
    (200, 4, 4, 64, 64, 256),
    (512, 8, 1, 128, 256, 256),
]


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("t,heads,kv_heads,block_q,block_k,d", PARITY)
def test_kernels_match_reference_in_float32(t, heads, kv_heads, block_q,
                                            block_k, d, causal):
    q, k, v, w = _qkv(t, heads, kv_heads, d, jnp.float32)
    plan = causal_plan(t, t, block_q, block_k, causal)
    if causal and t % block_q == 0:
        assert plan["skipped"] and plan["unmasked"] and plan["masked"]

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, interpret=True,
                               block_q=block_q, block_k=block_k)

    def ref(q, k, v):
        return reference_attention(q, k, v, causal=causal)

    assert float(jnp.abs(flash(q, k, v) - ref(q, k, v)).max()) <= 1e-4
    got = jax.grad(lambda *a: (flash(*a) * w).sum(), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: (ref(*a) * w).sum(), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        assert float(jnp.abs(a - b).max()) <= 1e-4, name


# a value head narrower than the score lanes (latent attention: 192 score
# lanes and 128 value lanes in ``longcat_flash``): as many key-value heads
# as query heads and four query heads a key-value head, whole and ragged
NARROW = [
    # t, heads, kv_heads, block_q, block_k, d, dv
    (512, 4, 4, 128, 256, 48, 32),
    (512, 4, 1, 256, 128, 48, 32),
    (200, 4, 4, 64, 64, 192, 128),
    (384, 8, 2, 128, 128, 192, 128),
]


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("t,heads,kv_heads,block_q,block_k,d,dv", NARROW)
def test_kernels_match_reference_at_a_narrower_value_head(
        t, heads, kv_heads, block_q, block_k, d, dv, causal):
    """q and k ``[.., d]``, v ``[.., dv]``: the output is ``[.., dv]``, and
    it and the three gradients are ``reference_attention``'s in float32
    under the interpreter (dq and dk at ``d`` lanes, dv at ``dv``)."""
    q, k, _, _ = _qkv(t, heads, kv_heads, d, jnp.float32)
    key = jax.random.key(9)
    v = jax.random.normal(key, (1, kv_heads, t, dv))
    w = jax.random.normal(jax.random.fold_in(key, 1), (1, heads, t, dv))

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, interpret=True,
                               block_q=block_q, block_k=block_k)

    def ref(q, k, v):
        return reference_attention(q, k, v, causal=causal)

    out = flash(q, k, v)
    assert out.shape == (1, heads, t, dv)
    assert float(jnp.abs(out - ref(q, k, v)).max()) <= 1e-4
    got = jax.grad(lambda *a: (flash(*a) * w).sum(), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: (ref(*a) * w).sum(), argnums=(0, 1, 2))(q, k, v)
    for name, a, b, width in zip(("dq", "dk", "dv"), got, want, (d, d, dv)):
        assert a.shape[-1] == width, name
        assert float(jnp.abs(a - b).max()) <= 1e-4, name


def test_a_head_of_192_lanes_gets_blocks_of_1024():
    """The cap on a block shrinks with the head past 128 lanes and is a
    power of two: 192 lanes (and 256) get blocks of 1024 at T4096, which
    divide the sequence; 128 lanes and fewer keep 2048."""
    assert [_block(4096, d, 512) for d in (64, 128, 192, 256)] == [
        2048, 2048, 1024, 1024]


def test_kernels_match_reference_in_bfloat16_with_derived_blocks():
    q, k, v, w = _qkv(640, 4, 2, 64, jnp.bfloat16)  # one 640 block, 5 strips

    def loss(fn):
        return lambda *a: (fn(*a).astype(jnp.float32) * w).sum()

    flash = lambda q, k, v: flash_attention(q, k, v, interpret=True)  # noqa: E731
    out = flash(q, k, v).astype(jnp.float32)
    ref = reference_attention(q, k, v).astype(jnp.float32)
    assert float(jnp.abs(out - ref).max()) <= 2e-2
    got = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(reference_attention), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        gap = jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max()
        assert float(gap) <= 2e-2 * max(1.0, float(jnp.abs(b).max()))


def _plan_events():
    return [r for r in get_tracer().records()
            if r["name"] == "kernel/flash/plan"]


def test_traced_kernels_leave_one_plan_event_each():
    q, k, v, _ = _qkv(384, 4, 2, 32, jnp.float32)

    @jax.jit
    def grads(q, k, v):
        return jax.grad(lambda *a: flash_attention(
            *a, interpret=True, block_q=128, block_k=256).sum(),
            argnums=(0, 1, 2))(q, k, v)

    before = len(_plan_events())
    grads(q, k, v)
    grads(q, k, v)  # the same signature: traced once, nothing per call
    events = _plan_events()[before:]
    assert sorted(e["attrs"]["kernel"] for e in events) == [
        "flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]
    plan = causal_plan(384, 384, 128, 256, True)
    for event in events:
        attrs = event["attrs"]
        assert (attrs["t"], attrs["s"], attrs["d"], attrs["v_dim"],
                attrs["heads"], attrs["kv_heads"]) == (384, 384, 32, 32, 4, 2)
        for key in ("visited", "masked", "unmasked", "skipped", "area_ratio"):
            assert attrs[key] == plan[key], key
