"""``apply_rope`` (the half-swap as a signed-permutation product, one pass
forward and one backward) against the split-and-concatenate form, which
is stated here as the plain statement of the repo's rotate-half
convention: values and gradients, in float32 to the bit and in bfloat16
after the one rounding, for full and partial rotary widths, 2-D tables
(training) and 3-D ones (the serving engine's per-row positions)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.ad_checkpoint import print_saved_residuals

from fedml_tpu import telemetry
from fedml_tpu.models.llm.layers import _swapped, apply_rope, rope_tables


def plain_rope(x, cos, sin):
    """Rotate-half on the first ``2 r`` lanes of a head, the rest passed
    through: float32 multiply-adds, one rounding to ``x.dtype``."""
    rot = 2 * cos.shape[-1]
    x1, x2 = jnp.split(x[..., :rot].astype(jnp.float32), 2, axis=-1)
    if cos.ndim == 2:
        cos, sin = cos[None, None], sin[None, None]
    else:
        cos, sin = cos[:, None], sin[:, None]
    turned = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)
    return jnp.concatenate([turned, x[..., rot:]], axis=-1)


# (batch, heads, tokens, head_dim, rotary_dim, per-row positions)
CASES = {
    "d64_full": (2, 4, 16, 64, 64, False),
    "d128_full": (1, 4, 8, 128, 128, False),
    "d128_half_zaya": (2, 2, 8, 128, 64, False),
    "d128_gqa_kv_heads": (1, 1, 16, 128, 128, False),
    "d64_rows_3d": (3, 4, 8, 64, 64, True),
    "d64_decode_t1": (3, 4, 1, 64, 64, True),
    "d128_half_decode_t1": (3, 2, 1, 128, 64, True),
}


def _case(name, dtype):
    b, h, t, d, rot, rows = CASES[name]
    kx, kg, kp = jax.random.split(jax.random.key(sum(map(ord, name))), 3)
    x = jax.random.normal(kx, (b, h, t, d), jnp.float32).astype(dtype)
    g = jax.random.normal(kg, (b, h, t, d), jnp.float32).astype(dtype)
    if rows:  # each row of the serving batch at its own position
        positions = jax.random.randint(kp, (b, 1), 0, 4000) + jnp.arange(t)
    else:
        positions = jnp.arange(t)
    cos, sin = rope_tables(positions, rot, 10000.0)
    assert cos.shape == positions.shape + (rot // 2,)
    return x, g, cos, sin


def _same(a, b):
    assert a.dtype == b.dtype and a.shape == b.shape
    np.testing.assert_array_equal(np.asarray(a, np.float32),
                                  np.asarray(b, np.float32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("name", list(CASES))
def test_values_are_the_plain_forms(name, dtype):
    x, _, cos, sin = _case(name, dtype)
    _same(jax.jit(apply_rope)(x, cos, sin), plain_rope(x, cos, sin))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("name", list(CASES))
def test_gradients_are_the_plain_forms(name, dtype):
    """The cotangent arrives in the compute type, as the flash kernels'
    does: float32 multiply-adds and one rounding, like the forward."""
    x, g, cos, sin = _case(name, dtype)
    (want,) = jax.vjp(lambda x: plain_rope(x, cos, sin), x)[1](g)
    (got,) = jax.jit(
        lambda x, g: jax.vjp(lambda x: apply_rope(x, cos, sin), x)[1](g)
    )(x, g)
    _same(got, want)


@pytest.mark.parametrize("name", ["d64_full", "d128_half_zaya",
                                  "d64_rows_3d"])
def test_the_tables_gradients_are_the_plain_forms(name):
    x, g, cos, sin = _case(name, jnp.float32)

    def through(rope):
        return jax.grad(
            lambda cos, sin: jnp.sum(rope(x, cos, sin) * g), argnums=(0, 1)
        )(cos, sin)

    for got, want in zip(through(apply_rope), through(plain_rope)):
        np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)


def test_the_product_is_exact_for_a_float32_input():
    """Every mantissa bit survives the swap: a bfloat16 pass of the MXU
    would keep 8. (On the CPU a float32 product is exact whatever is
    asked; what is held here is that full precision IS asked, below.)"""
    x = jnp.asarray(
        np.random.default_rng(0).standard_normal((1, 2, 4, 64)), jnp.float32)
    swapped = _swapped(x, 64)
    _same(swapped, jnp.concatenate([-x[..., 32:], x[..., :32]], -1))
    _same(_swapped(x, 64, back=True), -swapped)

    def precisions(dtype):
        jaxpr = jax.make_jaxpr(lambda x: _swapped(x, 64))(x.astype(dtype))
        return [e.params["precision"] for e in jaxpr.eqns
                if e.primitive.name == "dot_general"]

    highest = jax.lax.Precision.HIGHEST
    assert precisions(jnp.float32) == [(highest, highest)]
    assert precisions(jnp.float16) == [(highest, highest)]
    assert precisions(jnp.bfloat16) == [None]


@pytest.mark.parametrize("name,dtype", [
    ("d64_full", jnp.bfloat16), ("d128_half_zaya", jnp.bfloat16),
    ("d128_half_decode_t1", jnp.float32)])
def test_the_plan_event_is_left_once_a_trace(name, dtype):
    b, h, t, d, rot, _ = CASES[name]
    x, g, cos, sin = _case(name, dtype)
    tracer = telemetry.get_tracer()
    before = len([r for r in tracer.records() if r["name"] == "rope/plan"])
    fn = jax.jit(jax.grad(
        lambda x: jnp.sum(apply_rope(x, cos, sin).astype(jnp.float32))))
    fn(x)
    fn(x)  # compiled: not traced again
    events = [r for r in tracer.records() if r["name"] == "rope/plan"]
    assert len(events) == before + 1
    assert events[-1]["attrs"] == {
        "rows": b * t, "heads": h, "head_dim": d, "rotary_dim": rot,
        "dtype": jnp.dtype(dtype).name, "form": "product"}


def test_a_remat_policy_that_keeps_products_does_not_keep_this_one(capsys):
    """``remat_policy: dots`` saves a layer's products with its matrices;
    the swap's float32 output (twice q's bytes) is not one of them."""
    x, _, cos, sin = _case("d64_full", jnp.bfloat16)
    w = jnp.eye(64, dtype=jnp.bfloat16)

    def f(x, w):
        q = apply_rope(x @ w, cos, sin)
        return jnp.sum(q.astype(jnp.float32) ** 2)

    print_saved_residuals(jax.checkpoint(
        f, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable),
        x, w)
    kept = [line.split()[0] for line in capsys.readouterr().out.splitlines()]
    # of q's size: x itself and the projection's output, in the compute type
    assert [k for k in kept if "[2,4,16,64]" in k] == ["bf16[2,4,16,64]"] * 2


def test_no_split_and_no_concatenation_of_the_heads():
    """What XLA does not fuse is not in the lowered text: the only
    concatenations are the tables' (``[T, D]``), in either direction."""
    x, g, cos, sin = _case("d128_half_zaya", jnp.bfloat16)
    text = jax.jit(
        lambda x, g: jax.vjp(lambda x: apply_rope(x, cos, sin), x)[1](g)
    ).lower(x, g).as_text()
    assert "stablehlo.dot_general" in text
    for line in text.splitlines():
        if "stablehlo.concatenate" in line or "stablehlo.slice" in line:
            assert "tensor<8x128xf32>" in line.rsplit("->", 1)[-1], line
