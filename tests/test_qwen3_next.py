"""``model: qwen3_next`` against its plain float32 reference, on the CPU at
tiny widths with the published row's pattern and ratios (one whole period:
three Gated DeltaNet layers, then gated attention; two value heads a key
head; eight query heads a key-value head with a quarter of a head's lanes
rotary; a quarter of 16 experts held, top-3; a gated shared expert; an
untied head), on seeded random weights, with plain attention and with the
flash kernels under the interpreter; the chunked delta rule against the
sequential one; the held shares against the uncut layer; then through
``FedLLMAPI``'s fused round. A timing here is never a speed."""
import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu import telemetry
from fedml_tpu.models.llm import config_from_args
from fedml_tpu.models.llm import qwen3_next_reference as ref
from fedml_tpu.models.llm.layers import (RMSNorm, ZeroCenteredRMSNorm,
                                         apply_rope, rope_tables,
                                         softmax_topk)
from fedml_tpu.models.llm.qwen3_next import (Qwen3NextConfig,
                                             Qwen3NextGatedDeltaNet,
                                             Qwen3NextMoE)
from fedml_tpu.ops import gated_delta as gd
from fedml_tpu.ops.flash_attention import flash_attention
from fedml_tpu.ops.gated_delta import gated_delta
from fedml_tpu.train.llm.sharding import unbox
from fedml_tpu.train.llm.trainer import (extract_lora, extract_trainable,
                                         merge_lora)

B, T = 2, 20


def _path(path) -> str:
    return "/".join(str(getattr(p, "key", p)) for p in path)


def seeded(cfg, seed=0, shape=(B, T)):
    """``init``'s weights with every leaf that starts at 0 or 1 made random
    (``lora_b``, norm weights and scales, ``A_log``, ``dt_bias``) and the
    router's logits spread over more than rounding."""
    tokens = jax.random.randint(jax.random.key(seed + 1), shape, 0,
                                cfg.vocab_size)
    params = unbox(jax.jit(cfg.module().init)(jax.random.key(seed), tokens))
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for i, (path, v) in enumerate(flat):
        name, key = _path(path), jax.random.fold_in(jax.random.key(seed + 7), i)
        if "lora_b" in name:
            v = 0.05 * jax.random.normal(key, v.shape, v.dtype)
        elif name.endswith(("/weight", "scale", "A_log", "dt_bias")):
            v = v + 0.3 * jax.random.normal(key, v.shape, v.dtype)
        elif name.endswith("router_weight"):
            v = 3.0 * v
        out.append(v)
    return jax.tree_util.tree_unflatten(treedef, out), tokens


def _f32(**kw):
    return Qwen3NextConfig.tiny(lora_rank=4, dtype=jnp.float32,
                                param_dtype=jnp.float32, **kw)


@pytest.fixture(scope="module")
def f32():
    cfg = _f32()
    params, tokens = seeded(cfg)
    return cfg, params, tokens


def _interpreted(q, k, v):
    return flash_attention(q, k, v, causal=True, interpret=True)


def test_the_tiny_preset_keeps_the_rows_pattern_and_ratios():
    tiny, row = Qwen3NextConfig.tiny(), Qwen3NextConfig.qwen3_next_80b_a3b()
    assert [row.is_linear(i) for i in range(48)].count(False) == 12
    assert [tiny.is_linear(i) for i in range(4)] == [True, True, True, False]
    assert (row.rotary_dim, row.key_dim, row.value_dim, row.conv_dim) == (
        64, 2048, 4096, 8192)
    assert (row.experts_total, row.num_experts_per_tok) == (512, 10)
    assert (tiny.experts_total, tiny.num_experts) == (16, 4)
    for cfg in (tiny, row):
        assert cfg.num_attention_heads // cfg.num_key_value_heads == 8
        assert cfg.head_dim == 4 * cfg.rotary_dim
        assert cfg.linear_num_value_heads == 2 * cfg.linear_num_key_heads
        assert cfg.linear_key_head_dim == cfg.linear_value_head_dim
        assert 4 * cfg.moe_intermediate_size == cfg.hidden_size \
            == 4 * cfg.shared_expert_intermediate_size
    for key in ("linear_conv_kernel_dim", "full_attention_interval",
                "norm_topk_prob", "tie_word_embeddings", "rms_norm_eps",
                "rope_theta", "partial_rotary_factor"):
        assert getattr(tiny, key) == getattr(row, key), key


@pytest.mark.parametrize("bad", [
    {"tie_word_embeddings": True}, {"attention_bias": True},
    {"use_sliding_window": True}, {"rope_scaling": {"type": "yarn"}},
    {"hidden_act": "gelu"}, {"decoder_sparse_step": 2},
    {"mlp_only_layers": (1,)}, {"num_key_value_heads": 3},
    {"linear_num_value_heads": 5}, {"partial_rotary_factor": 0.3},
    {"partial_rotary_factor": 0.1875},
    {"norm_topk_prob": False}, {"num_experts_per_tok": 17},
    {"held_experts_first": 13}])
def test_what_is_not_implemented_is_refused(bad):
    with pytest.raises(ValueError, match="not implemented"):
        Qwen3NextConfig.tiny(**bad)


# -- the delta rule ---------------------------------------------------------
def _delta_inputs(t, heads=3, dk=16, dv=8, decay=(-3.0, 0.0),
                  beta=(-3.0, 3.0), seed=0):
    """q, k unit-norm as the layer hands them (q scaled), out of a silu
    over a direction they share, as a layer's are (so keys lie in one
    orthant and are correlated: the case a triangular inverse in float32 can
    get wrong); log-decays and write strengths spread from ``decay`` /
    ``beta`` (the logits' range) over the heads: each head's own, so that
    one holds its state across chunks and another forgets within a
    token."""
    keys = jax.random.split(jax.random.key(seed), 6)
    common = jax.random.normal(keys[5], (dk,))
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    qk = lambda key: unit(jax.nn.silu(
        jax.random.normal(key, (2, t, heads, dk)) + common))
    q, k = qk(keys[0]) / np.sqrt(dk), qk(keys[1])
    v = jax.random.normal(keys[2], (2, t, heads, dv))
    spread = lambda lo_hi, key: jnp.linspace(*lo_hi, heads) + 0.3 * \
        jax.random.normal(key, (2, t, heads))
    g = -jnp.exp(spread(decay, keys[3]))   # log-decays, -20 .. -0.002
    b = jax.nn.sigmoid(spread(beta, keys[4]))
    return q, k, v, g, b


_SEQUENTIAL = jax.vmap(ref.recurrence)
# the two forms of ops/gated_delta.py: the chunked XLA form (off a TPU, the
# default) and the kernels under the Pallas interpreter
_FORMS = {"chunked_xla": None, "kernel": True}


def _assert_values_and_gradients_agree(op, args, valid=None):
    """``op`` against the sequential loop over ``args`` (or, where
    ``valid`` tokens are given, over their first ``valid`` only), in
    float32: values to 2e-5 of values of order 1; the gradient with
    respect to all five inputs to 1e-4 of each gradient's largest entry,
    or of a thousandth of the largest entry of all five, whichever is more
    (the five come out of the same sums, and where every decay is near 0
    ``g``'s own gradient is of the size of their rounding)."""
    cut = (lambda a: a[:, :valid]) if valid else (lambda a: a)
    with jax.default_matmul_precision("highest"):
        want = _SEQUENTIAL(*map(cut, args))
        np.testing.assert_allclose(cut(op(*args)), want, atol=2e-5, rtol=0)
        weigh = jax.random.normal(jax.random.key(9), want.shape)
        g = jax.grad(lambda *a: jnp.sum(cut(op(*a)) * weigh),
                     argnums=(0, 1, 2, 3, 4))(*args)
        w = jax.grad(lambda *a: jnp.sum(_SEQUENTIAL(*a) * weigh),
                     argnums=(0, 1, 2, 3, 4))(*map(cut, args))
    largest = max(float(jnp.abs(b).max()) for b in w)
    for name, a, b in zip("q k v g beta".split(), g, w):
        scale = max(float(jnp.abs(b).max()), 1e-3 * largest)
        assert scale > 0, name
        np.testing.assert_allclose(cut(a), b, atol=1e-4 * scale, rtol=0,
                                   err_msg=name)


@pytest.mark.parametrize("form", list(_FORMS))
@pytest.mark.parametrize("t,chunk", [(8, 8), (16, 8), (24, 8), (12, 4),
                                     (64, 64)],
                         ids=["1_chunk", "2_chunks", "3_chunks",
                              "3_small_chunks", "1_chunk_of_64"])
@pytest.mark.parametrize("decay,beta", [
    ((-3.0, 3.0), (-3.0, 3.0)), ((-6.0, -3.0), (3.0, 6.0)),
    ((2.0, 3.0), (-6.0, -3.0))],
    ids=["spread", "decay_near_1_beta_near_1", "decay_near_0_beta_near_0"])
def test_the_chunked_delta_rule_is_the_sequential_one(t, chunk, decay, beta,
                                                      form):
    """``ops/gated_delta.py`` (chunks, the inverse by halving, one state a
    chunk), in either form, against the token-by-token loop of the
    reference (:func:`_assert_values_and_gradients_agree`): within one
    chunk, across one boundary and two, over a chunk of 64 at keys of 128
    lanes, with heads whose decay is near 1 (a state kept across chunks) or
    near 0 (forgotten within a token) and whose beta is near 1 or near 0;
    keys out of a silu, in one orthant."""
    wide = dict(dk=128, dv=32) if chunk == 64 else {}
    args = _delta_inputs(t, decay=decay, beta=beta, **wide)
    _assert_values_and_gradients_agree(
        lambda *a: gated_delta(*a, chunk=chunk, interpret=_FORMS[form]), args)


@pytest.mark.parametrize("form", list(_FORMS))
def test_a_padded_tail_changes_nothing(form):
    """Heads of 128 lanes (what the compiled kernels tile), three chunks
    of 16 of which the last holds 5 tokens of padding as the layer pads
    (``q = k = v = 0``, ``g = beta = 0``): the first 43 tokens' values and
    gradients are the sequential loop's over those 43 alone."""
    valid, t = 43, 48
    args = _delta_inputs(valid, heads=2, dk=128, dv=128)
    args = [jnp.pad(a, [(0, 0), (0, t - valid)] + [(0, 0)] * (a.ndim - 2))
            for a in args]
    _assert_values_and_gradients_agree(
        lambda *a: gated_delta(*a, chunk=16, interpret=_FORMS[form]), args,
        valid=valid)


def _inverse_in_a_kernel(a):
    """The kernels' inverse of one ``[C, C]`` matrix, run inside a
    ``pallas_call`` under the interpreter."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(a_ref, t_ref, work):
        t_ref[...] = gd._vmem_inverse(a_ref[...], work)

    return pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
        scratch_shapes=[pltpu.VMEM(a.shape, a.dtype)], interpret=True)(a)


@pytest.mark.parametrize("form", list(_FORMS))
def test_the_inverse_by_halving_is_the_inverse(form):
    """``(I + A)^-1`` of a chunk of 64 correlated keys of 128 lanes with
    beta near 1, against float64's: float32 rounding (the doubling product
    ``(I - A)(I + A^2)...``, exact in exact arithmetic, misses such a chunk
    by orders of magnitude in float32: its powers of ``A`` grow before they
    cancel); also at a chunk that is no power of two and at 128. The
    chunked form's batched halving and the kernels' halving on whole
    matrices alike."""
    inverse = {"chunked_xla": lambda a: gd._inverse(a[None])[0],
               "kernel": _inverse_in_a_kernel}[form]
    for c in (64, 12, 128):
        _, k, _, _, _ = _delta_inputs(c, heads=1, dk=128)
        keys = np.asarray(k[0, :, 0], np.float64)
        a = 0.95 * np.tril(keys @ keys.T, -1)
        want = np.linalg.inv(np.eye(c) + a)
        got = np.asarray(inverse(jnp.asarray(a, jnp.float32)))
        np.testing.assert_allclose(got, want, atol=1e-6, rtol=0)


@pytest.mark.parametrize("form", list(_FORMS))
def test_the_delta_rule_in_bfloat16_stays_near_float32(form):
    """The program's form: bfloat16 inputs, every product's operands in
    bfloat16 (the inverse and the carried state float32): within a few
    bfloat16 roundings of the float32 loop over three chunks of 64 (heads
    of 128 lanes for the kernels, as they are compiled)."""
    width = dict(heads=4, dk=32, dv=32) if form == "chunked_xla" else dict(
        heads=2, dk=128, dv=128)
    args = _delta_inputs(192, **width)
    low = [a.astype(jnp.bfloat16) if i < 3 else a for i, a in enumerate(args)]
    out = gated_delta(*low, chunk=64, interpret=_FORMS[form])
    got = out.astype(jnp.float32)
    want = _SEQUENTIAL(*[a.astype(jnp.float32) for a in low])
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got - want).max()) < 2e-2 * scale
    assert out.dtype == jnp.bfloat16


def test_the_delta_rule_refuses_a_part_of_a_chunk():
    with pytest.raises(ValueError, match="whole number of chunks"):
        gated_delta(*_delta_inputs(12), chunk=8)


@pytest.mark.parametrize("form", list(_FORMS))
def test_the_mixer_leaves_its_plan(f32, form, monkeypatch):
    """The ``gdn/plan`` event names the form the op computes: off a TPU
    the chunked form, which writes ``[B, chunks, value heads, dk, dv]``
    float32 chunk states, every head at once; under the interpreter (as on
    a TPU) the kernels, whose state is a step's heads in VMEM; and the
    layer's output is the same either way."""
    cfg, params, tokens = f32
    if form == "kernel":
        monkeypatch.setattr(gd, "kernel_mode",
                            lambda *a, **kw: gd.INTERPRET)
    telemetry.reset_tracer()
    u = jax.random.normal(jax.random.key(2), (B, T, cfg.hidden_size))
    layer = lambda: Qwen3NextGatedDeltaNet(cfg).apply(
        {"params": params["params"]["layer_0"]["linear_attn"]}, u)
    got = layer()
    (plan,) = [r for r in telemetry.get_tracer().records()
               if r["name"] == "gdn/plan"]
    # T 20 padded to 3 chunks of 8; 4 value heads: one step holds them all
    state_bytes, inverse = {
        "chunked_xla": (B * 3 * 4 * 16 * 16 * 4, "halving"),
        "kernel": (4 * 16 * 16 * 4, "substitution_halving")}[form]
    assert plan["point"] and plan["attrs"] == {
        "rows": B * T, "key_heads": 2, "value_heads": 4, "key_dim": 16,
        "value_dim": 16, "chunk": 8, "chunks": 3, "form": form,
        "heads_per_step": 4, "inverse": inverse,
        "state_bytes": state_bytes, "dtype": "float32"}
    monkeypatch.undo()
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(got, layer(), rtol=0, atol=2e-5)
    telemetry.reset_tracer()


# -- the layers' shapes -------------------------------------------------------
def test_the_projections_columns_are_grouped_a_key_head():
    """Step 1's and 2's layout against an explicit construction: column
    ``c`` of ``W_qkvz`` (``W_ba``) reads ``c``, and each part the reference
    takes holds the columns the published grouping gives it — key head
    ``h`` owns ``[q_h | k_h | v_2h, v_2h+1 | z_2h, z_2h+1]`` (``[b_2h,
    b_2h+1 | a_2h, a_2h+1]``)."""
    cfg = Qwen3NextConfig.tiny()
    hk, dk, dv = 2, 16, 16
    width = 2 * dk + 2 * 2 * dv
    q, k, v, z = ref.split_qkvz(cfg, jnp.arange(hk * width)[None])
    for h in range(hk):
        base = h * width
        np.testing.assert_array_equal(q[0, h], base + np.arange(dk))
        np.testing.assert_array_equal(k[0, h], base + dk + np.arange(dk))
        for j in range(2):
            np.testing.assert_array_equal(
                v[0, 2 * h + j], base + 2 * dk + j * dv + np.arange(dv))
            np.testing.assert_array_equal(
                z[0, 2 * h + j], base + 2 * dk + (2 + j) * dv + np.arange(dv))
    b, a = ref.split_ba(cfg, jnp.arange(hk * 4)[None])
    np.testing.assert_array_equal(b[0], [0, 1, 4, 5])
    np.testing.assert_array_equal(a[0], [2, 3, 6, 7])


def test_rotary_turns_the_leading_64_of_256_lanes():
    """The shell's tables at 64 rotary lanes and ``apply_rope`` on whole
    heads of 256 against the reference's split form: lane ``j`` with lane
    ``j + 32`` turned, lanes 64-255 unchanged."""
    x = jax.random.normal(jax.random.key(4), (1, 2, 12, 256))
    cos, sin = rope_tables(jnp.arange(12), 64, 1e7)
    got = apply_rope(x, cos, sin)
    want = ref.rotary(x[0].transpose(1, 0, 2), 64, 1e7).transpose(1, 0, 2)
    np.testing.assert_allclose(got[0], want, atol=2e-6, rtol=0)
    np.testing.assert_array_equal(got[..., 64:], x[..., 64:])
    assert float(jnp.abs(got[..., 1:, :64] - x[..., 1:, :64]).max()) > 0.1


def test_the_zero_centred_norm_at_zero_is_a_plain_rms_norm():
    x = jax.random.normal(jax.random.key(5), (3, 7, 24))
    zero = ZeroCenteredRMSNorm(1e-6, jnp.float32)
    plain = RMSNorm(1e-6, jnp.float32)
    got = zero.apply(zero.init(jax.random.key(0), x), x)
    want = plain.apply(plain.init(jax.random.key(0), x), x)
    np.testing.assert_array_equal(got, want)
    # and a weight w scales by 1 + w
    w = jnp.linspace(-0.5, 0.5, 24)
    np.testing.assert_allclose(
        zero.apply({"params": {"weight": w}}, x),
        plain.apply({"params": {"scale": 1.0 + w}}, x), rtol=1e-6)


class _Router(nn.Module):
    total: int
    k: int

    @nn.compact
    def __call__(self, u):
        return softmax_topk(self, u, self.total, self.k)


def test_a_softmax_top_10_tie_goes_to_the_lower_experts():
    """Twelve experts with the same logit, above the other four: the ten
    of them with the lowest index are chosen, each weighed a tenth."""
    router = _Router(16, 10)
    u = jnp.ones((3, 4))
    w = jnp.zeros((4, 16)).at[:, 2:14].set(1.0)
    chosen, weights = router.apply({"params": {"router_weight": w}}, u)
    np.testing.assert_array_equal(jnp.sort(chosen, axis=-1),
                                  np.tile(np.arange(2, 12), (3, 1)))
    np.testing.assert_allclose(weights[:, 2:12], 0.1, rtol=1e-6)
    assert float(jnp.abs(weights[:, 12:]).max()) == 0.0
    assert float(jnp.abs(weights[:, :2]).max()) == 0.0


# -- the model against the reference -----------------------------------------
@pytest.mark.parametrize("attention", ["plain", "flash_interpreted"])
def test_logits_loss_and_counts_are_the_references(f32, attention):
    """float32 on both sides: what is left is the order of summation (5e-6
    of logits of order 1) — the chunked delta rule against the sequential
    one, most of it (3.4e-6 at most here; 3e-7 with the loop in the
    module's place: each head's output is RMS-normed, which carries the
    chunked form's relative rounding through whatever the output's size),
    the sorted grouped product against the loop with a mask, one fused rope
    pass against the split form, and under the interpreter the online
    softmax in blocks."""
    cfg, params, tokens = f32
    fn = _interpreted if attention == "flash_interpreted" else None
    logits, state = cfg.module().apply(
        params, tokens, attention_fn=fn, mutable=["intermediates"])
    want, want_counts = ref.forward(cfg, params, tokens)
    np.testing.assert_allclose(logits, want, atol=5e-6, rtol=0)
    sown = {k: v[0] for k, v in state["intermediates"].items()}
    assert set(sown) == set(cfg.round_stats)
    np.testing.assert_array_equal(sown["moe_tokens"], want_counts)
    assert sown["moe_tokens"].shape == (4, cfg.num_experts)
    # every assignment to a held expert got a row: nothing dropped
    np.testing.assert_array_equal(sown["moe_placed"], sown["moe_held"])
    np.testing.assert_array_equal(sown["moe_tokens"].sum(1), sown["moe_held"])
    assert 0 < int(sown["moe_held"].sum()) < 4 * B * T * 3


def _losses(cfg, params, tokens, attention_fn=None):
    targets = jnp.roll(tokens, -1, axis=1)

    def module_loss(lora):
        logits = cfg.module().apply(merge_lora(params, lora), tokens,
                                    attention_fn=attention_fn)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))

    def reference_loss(lora):
        return ref.loss(cfg, merge_lora(params, lora), tokens, targets)

    return module_loss, reference_loss


@pytest.mark.parametrize("attention", ["plain", "flash_interpreted"])
def test_every_adapter_leafs_gradient_is_the_references(f32, attention):
    """``jax.grad`` of the module's loss against ``jax.grad`` of the plain
    reference's (through the sequential delta rule), for all 20 adapter
    leaves; float32 both, so 1e-4 of the leaf's largest entry covers the
    order of summation."""
    cfg, params, tokens = f32
    fn = _interpreted if attention == "flash_interpreted" else None
    module_loss, reference_loss = _losses(cfg, params, tokens, fn)
    lora = extract_lora(params)
    assert len(lora) == 2 * (3 * 2 + 4)
    assert {k.split("/")[-2] for k in lora} == {
        "q_proj", "k_proj", "v_proj", "o_proj", "in_proj_qkvz", "out_proj"}
    loss, got = jax.jit(jax.value_and_grad(module_loss))(lora)
    want_loss, want = jax.jit(jax.value_and_grad(reference_loss))(lora)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    for k in lora:
        scale = float(jnp.abs(want[k]).max())
        assert scale > 0, k
        np.testing.assert_allclose(got[k], want[k], rtol=0, atol=1e-4 * scale,
                                   err_msg=k)


def test_a_module_computing_in_bfloat16_fails_these_tolerances(f32):
    """The tolerances above are tight enough to tell the precision: the
    same weights through a module whose compute type is bfloat16 miss the
    logits by a thousand times ``atol`` and a gradient by more than 1e-4
    of its scale."""
    cfg, params, tokens = f32
    low = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    want, _ = ref.forward(cfg, params, tokens)
    got = low.module().apply(params, tokens)
    assert float(jnp.abs(got - want).max()) > 3e-3
    module_loss, reference_loss = _losses(low, params, tokens)
    lora = extract_lora(params)
    got, want = (jax.jit(jax.grad(f))(lora)
                 for f in (module_loss, reference_loss))
    worst = max(float(jnp.abs(got[k] - want[k]).max()
                      / jnp.abs(want[k]).max()) for k in lora)
    assert worst > 1e-3


def test_only_the_adapters_train_and_travel(f32):
    """The router's leaf is ``router_weight``, not the key ``router`` that
    ``is_trainable_path`` catches: what the optimizer sees and what the
    round exchanges are the same 20 leaves."""
    _, params, _ = f32
    assert set(extract_trainable(params)) == set(extract_lora(params))
    assert not any(part in k for k in extract_trainable(params)
                   for part in ("router", "experts", "shared", "norm",
                                "in_proj_ba", "conv", "A_log", "dt_bias"))


@pytest.mark.parametrize("t", [0, 7, T - 2])
def test_no_logit_sees_a_later_token(f32, t):
    """Changing every token after ``t`` moves no logit at or before ``t``:
    a convolution, a chunk or a state that looked ahead would."""
    cfg, params, tokens = f32
    other = tokens.at[:, t + 1:].set((tokens[:, t + 1:] + 3) % cfg.vocab_size)
    a = cfg.module().apply(params, tokens)
    b = cfg.module().apply(params, other)
    np.testing.assert_allclose(a[:, :t + 1], b[:, :t + 1], atol=1e-6, rtol=0)
    assert float(jnp.abs(a[:, t + 1:] - b[:, t + 1:]).max()) > 1e-3


def test_the_held_shares_add_up_to_the_uncut_layer(f32):
    """The share test: one expert layer's router, 16 experts; the four
    chips of the deployment hold 4 each. What each share's module gives for
    its held range, less the gated shared expert (which every chip computes
    alike, so it is counted once), added over the four ranges equals the
    reference's uncut layer (all 16 held)."""
    cfg, _, _ = f32
    whole = _f32(num_experts=16, num_experts_total=16)
    u = jax.random.normal(jax.random.key(3), (B, T, cfg.hidden_size))
    p = unbox(Qwen3NextMoE(whole).init(jax.random.key(4), u))["params"]
    p["router_weight"] = 3.0 * p["router_weight"]
    flat_u = u.reshape(B * T, -1)
    with jax.default_matmul_precision("highest"):
        want, counts = ref.moe(whole, flat_u, p)
        shared = ref.shared(whole, flat_u, p)
    assert int(counts.sum()) == B * T * 3
    total, held = jnp.zeros_like(want), 0
    for first in (0, 4, 8, 12):
        share = _f32(held_experts_first=first)
        mine = dict(p, experts={k: v[first:first + 4]
                                for k, v in p["experts"].items()})
        out, stats = Qwen3NextMoE(share).apply({"params": mine}, u)
        np.testing.assert_array_equal(stats["moe_tokens"],
                                      counts[first:first + 4])
        assert int(stats["moe_placed"]) == int(stats["moe_held"])
        total = total + out.reshape(B * T, -1) - shared
        held += int(stats["moe_held"])
        # and the reference's own share is the module's
        with jax.default_matmul_precision("highest"):
            ref_share, _ = ref.moe(share, flat_u, mine)
        np.testing.assert_allclose(out.reshape(B * T, -1), ref_share,
                                   atol=2e-5, rtol=0)
    assert held == B * T * 3
    np.testing.assert_allclose(total + shared, want, atol=3e-5, rtol=0)


def test_the_yaml_names_the_model():
    class Args:
        model, model_size, lora_rank = "qwen3_next", "tiny", 4
        num_experts, held_experts_first = 8, 8

    cfg = config_from_args(Args(), vocab_size=99)
    assert isinstance(cfg, Qwen3NextConfig) and cfg.vocab_size == 99
    assert (cfg.num_experts, cfg.held_experts_first, cfg.experts_total,
            cfg.lora_rank) == (8, 8, 16, 4)
    assert type(cfg.module()).__name__ == "Qwen3NextForCausalLM"
    assert [cfg.module().layer_block(i).linear for i in range(4)] == [
        True, True, True, False]
    class Row:
        model, model_size, lora_rank = ("qwen3_next",
                                        "Qwen3-Next-80B-A3B-Instruct", 16)

    assert config_from_args(Row()) == Qwen3NextConfig(lora_rank=16)
    from fedml_tpu.models import model_hub

    assert type(model_hub.create(Args(), 64)).__name__ == \
        "Qwen3NextForCausalLM"


def test_a_cache_is_refused(f32):
    cfg, params, tokens = f32
    with pytest.raises(NotImplementedError, match="serving"):
        cfg.module().apply(params, tokens, kv_caches=[()] * 4)


def _api(on_device: bool):
    import fedml_tpu
    from fedml_tpu.arguments import load_arguments_from_dict
    from fedml_tpu.data import load_federated
    from fedml_tpu.train.llm.run_fedllm import FedLLMAPI

    never = 1 << 30
    args = fedml_tpu.init(load_arguments_from_dict({
        "common_args": {"training_type": "simulation", "random_seed": 0},
        "data_args": {"dataset": "synthetic_lm", "max_seq_length": 16,
                      "vocab_size": 64, "train_size": 64, "test_size": 16},
        "model_args": {"model": "qwen3_next", "model_size": "tiny",
                       "lora_rank": 4, "use_flash_attention": False},
        "train_args": {"federated_optimizer": "FedAvg",
                       "client_num_in_total": 4, "client_num_per_round": 2,
                       "comm_round": never, "frequency_of_the_test": never,
                       "local_steps_per_round": 2, "epochs": 2,
                       "per_device_batch_size": 1, "learning_rate": 5e-3,
                       "on_device_round": on_device},
    }))
    return FedLLMAPI(args, None, load_federated(args), mesh=None)


def test_the_fused_round_of_a_tiny_qwen3_next_is_the_host_loops(monkeypatch):
    """``fedml_tpu.init`` -> ``FedLLMAPI(on_device_round: true)`` ->
    ``train_one_round``: the same ``compile_federated_round`` as the other
    families', whose fifth output becomes the ``round/<n>/moe`` event with
    the held range's counts; the mixers leave ``gdn/plan``; and that
    program against the host loop it replaces, from the same state on the
    same rows. In float32 compute (the tiny preset's, steered here): 1e-4
    of an adapter's largest entry covers the two programs' orders of
    summation. In bfloat16 on the 8 virtual devices they reduce the batch
    in other orders and this tiny model's adapters move by up to 8 % with
    that after two Adam steps (a few % is what bfloat16 against float32
    reads at these widths, in this family as in ``nemotron_h``), so the
    bfloat16 round is held to the reference by the benchmark's own test
    (``benchmarks/tests/test_qwen3_next_family.py``) instead."""
    from fedml_tpu.ml.aggregator.agg_operator import FedMLAggOperator
    from fedml_tpu.telemetry.profiling import get_catalog

    tiny = Qwen3NextConfig.tiny
    monkeypatch.setattr(Qwen3NextConfig, "tiny", staticmethod(
        lambda **kw: tiny(**{"dtype": jnp.float32, **kw})))
    telemetry.reset_tracer()
    api = _api(on_device=True)
    assert isinstance(api.cfg, Qwen3NextConfig)
    engine = api.client.engine
    copy = lambda t: jax.tree.map(jnp.copy, t)
    p0, o0 = copy(engine.params), copy(engine.opt_state)
    g0 = copy(api.global_exchange)

    report = api.train_one_round(1)
    assert np.isfinite(report["train_loss"])
    (record,) = [r for r in get_catalog().records()
                 if r.name == "llm/fused_round"]
    assert record.calls == 1 and record.fallback_calls == 0
    records = telemetry.get_tracer().records()
    (moe,) = [r for r in records if r["name"] == "round/1/moe"]
    cfg = api.cfg
    tokens = 2 * 2 * engine.batch_size * engine.seq_len
    attrs = moe["attrs"]
    assert moe["point"] and attrs["dropped"] == 0
    assert (attrs["layers"], attrs["experts"], attrs["held"],
            attrs["top_k"]) == (4, 16, 4, 3)
    assert attrs["tokens"] == tokens and attrs["steps"] == 2 * 2
    assert attrs["assignments"] == tokens * 3
    assert 0.0 < attrs["held_share"] < 1.0
    assert attrs["capacity_rows"] == cfg.moe_capacity_rows(
        engine.batch_size * engine.seq_len)
    assert 1.0 <= attrs["max_over_mean"] <= cfg.num_experts
    assert 0.0 < attrs["live_share"] <= 1.0
    plans = [r["attrs"] for r in records if r["name"] == "gdn/plan"]
    assert plans and all(
        (p["key_heads"], p["value_heads"], p["key_dim"], p["chunk"],
         p["chunks"], p["form"], p["dtype"])
        == (2, 4, 16, 8, 2, "chunked_xla", "float32") for p in plans)
    names = [r["name"] for r in records]
    assert names.index("round/1/wait") < names.index("round/1/moe") \
        < names.index("round/1/run")

    # the host loop on the rows the round staged (the same seeded draws)
    from fedml_tpu.simulation.sampling import sample_clients

    rng = np.random.default_rng(int(api.args.random_seed) * 9973 + 1)
    p, o, uploads, weights = p0, o0, [], []
    for cid in sample_clients(api.args, 1):
        x, y = (np.asarray(a) for a in api.dataset.train_data_local_dict[cid])
        idx = rng.integers(0, x.shape[0], size=(2, engine.batch_size))
        p = merge_lora(p, copy(g0))
        for s in range(2):
            p, o, _ = engine._train_step(
                p, o, jnp.asarray(x[idx[s]][None]), jnp.asarray(y[idx[s]][None]),
                jnp.ones((1, engine.batch_size), jnp.float32))
        uploads.append(copy(extract_lora(p)))
        weights.append(float(api.dataset.train_data_local_num_dict[cid]))
    host = FedMLAggOperator.agg_with_weights(uploads, weights)
    assert set(host) == set(api.global_exchange)
    assert len(host) == 20
    for k, v in host.items():
        scale = float(jnp.abs(v).max())
        np.testing.assert_allclose(api.global_exchange[k], v, rtol=0,
                                   atol=1e-4 * scale, err_msg=k)
        assert float(jnp.abs(v - g0[k]).max()) > 0, k  # and it moved
    telemetry.reset_tracer()
