"""``model: glm4_moe_lite`` against its plain float32 reference, on the CPU
at tiny widths with the published row's shape (one leading dense layer,
then expert layers; rotary lanes a quarter of a head; latents of 3 : 2;
top-2 of 8 gated experts beside a shared one; an untied head), on seeded
random weights, with plain attention and with the flash kernels under the
interpreter; the routing's corner cases; then through ``FedLLMAPI``'s fused
round. A timing here is never a speed."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu import telemetry
from fedml_tpu.models.llm import config_from_args
from fedml_tpu.models.llm import glm_moe_lite_reference as ref
from fedml_tpu.models.llm.glm_moe_lite import GlmMoeLiteConfig, GlmMoeLiteMoE
from fedml_tpu.models.llm.layers import LatentAttention, published_lanes
from fedml_tpu.ops import grouped_matmul as gmm
from fedml_tpu.ops.flash_attention import flash_attention
from fedml_tpu.train.llm.sharding import unbox
from fedml_tpu.train.llm.trainer import (extract_lora, extract_trainable,
                                         merge_lora)

B, T = 2, 20


def _path(path) -> str:
    return "/".join(str(getattr(p, "key", p)) for p in path)


def seeded(cfg, seed=0, shape=(B, T)):
    """``init``'s weights with every leaf that starts at 0 or 1 made random
    (``lora_b``, the selection bias, norm scales) and the router's logits
    spread over more than rounding."""
    tokens = jax.random.randint(jax.random.key(seed + 1), shape, 0,
                                cfg.vocab_size)
    params = unbox(jax.jit(cfg.module().init)(jax.random.key(seed), tokens))
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for i, (path, v) in enumerate(flat):
        name, key = _path(path), jax.random.fold_in(jax.random.key(seed + 7), i)
        if "lora_b" in name or name.endswith("router_bias"):
            v = 0.05 * jax.random.normal(key, v.shape, v.dtype)
        elif name.endswith("scale"):
            v = v + 0.3 * jax.random.normal(key, v.shape, v.dtype)
        elif name.endswith("router_weight"):
            v = 3.0 * v
        out.append(v)
    return jax.tree_util.tree_unflatten(treedef, out), tokens


def _f32(**kw):
    return GlmMoeLiteConfig.tiny(lora_rank=4, dtype=jnp.float32,
                                 param_dtype=jnp.float32, **kw)


@pytest.fixture(scope="module")
def f32():
    cfg = _f32()
    params, tokens = seeded(cfg)
    return cfg, params, tokens


def _interpreted(q, k, v):
    return flash_attention(q, k, v, causal=True, interpret=True)


def test_the_tiny_preset_keeps_the_rows_shape_and_ratios():
    tiny, row = GlmMoeLiteConfig.tiny(), GlmMoeLiteConfig.glm_4_7_flash()
    assert (row.num_hidden_layers, row.first_k_dense_replace,
            row.expert_layers) == (47, 1, 46)
    assert (row.head_dim, row.rotary_dim, row.v_head_dim) == (256, 64, 256)
    assert (row.q_lora_rank, row.kv_lora_rank) == (768, 512)
    assert (row.n_routed_experts, row.num_experts_per_tok) == (64, 4)
    assert [tiny.is_dense(i) for i in range(3)] == [True, False, False]
    for cfg in (tiny, row):
        assert cfg.first_k_dense_replace == 1
        assert cfg.head_dim == 4 * cfg.rotary_dim == cfg.v_head_dim
        assert 2 * cfg.q_lora_rank == 3 * cfg.kv_lora_rank
        assert cfg.intermediate_size == 5 * cfg.hidden_size
        assert 4 * cfg.moe_intermediate_size == 3 * cfg.hidden_size
        assert cfg.num_key_value_heads == cfg.num_attention_heads
    for key in ("routed_scaling_factor", "norm_topk_prob", "topk_method",
                "tie_word_embeddings", "n_shared_experts", "rope_theta"):
        assert getattr(tiny, key) == getattr(row, key), key


@pytest.mark.parametrize("bad", [
    {"tie_word_embeddings": True}, {"attention_bias": True},
    {"num_key_value_heads": 2}, {"topk_group": 2}, {"qk_rope_head_dim": 3},
    {"rope_scaling": {"type": "yarn"}}, {"hidden_act": "gelu"},
    {"topk_method": "greedy"}, {"n_group": 2}, {"norm_topk_prob": False},
    {"n_shared_experts": 2}, {"num_experts_per_tok": 9},
    {"first_k_dense_replace": 4}])
def test_what_is_not_implemented_is_refused(bad):
    if "qk_rope_head_dim" in bad:   # keep the head's size: 13 + 3 lanes
        bad = dict(bad, qk_nope_head_dim=13)
    with pytest.raises(ValueError, match="not implemented"):
        GlmMoeLiteConfig.tiny(**bad)


def test_a_value_head_narrower_than_the_score_lanes_is_taken():
    """Since the latent attention is shared with ``longcat_flash`` (192
    score lanes, 128 value lanes), a narrower value head is no longer
    refused: the attention's output projection takes ``H x v_head_dim``."""
    cfg = GlmMoeLiteConfig.tiny(v_head_dim=8)
    u = jnp.ones((1, 4, cfg.hidden_size))
    cos, sin = (jnp.ones((4, cfg.rotary_dim // 2)),
                jnp.zeros((4, cfg.rotary_dim // 2)))
    module = LatentAttention(cfg)
    params = unbox(module.init(jax.random.key(0), u, cos, sin))["params"]
    assert params["o_proj"]["kernel"].shape == (
        cfg.num_attention_heads * 8, cfg.hidden_size)
    assert module.apply({"params": params}, u, cos, sin).shape == u.shape


def test_the_published_lane_order_is_a_permutation_a_head():
    cfg = GlmMoeLiteConfig.tiny()
    lanes = published_lanes(cfg)
    assert sorted(lanes) == list(range(4 * 16))
    # a head's first published lane is its first nope lane, which lies
    # after the 4 rope lanes here; its last 4 are the rope lanes
    assert lanes[:3].tolist() == [4, 5, 6] and lanes[12:16].tolist() == [
        0, 1, 2, 3]
    assert lanes[16:19].tolist() == [20, 21, 22]
    row = published_lanes(GlmMoeLiteConfig.glm_4_7_flash())
    assert row.shape == (5120,) and row[:2].tolist() == [64, 65]
    assert row[192:194].tolist() == [0, 1] and row[256] == 256 + 64


@pytest.mark.parametrize("attention", ["plain", "flash_interpreted"])
def test_logits_loss_and_counts_are_the_references(f32, attention):
    """float32 on both sides, the reference in the PUBLISHED lane order
    (``ref.published`` moves ``q_b_proj``'s columns): what is left is the
    order of summation (1e-6 of logits of order 1) — the sorted grouped
    product against the loop with a mask, one fused rope pass against the
    split form, and under the interpreter the online softmax in blocks."""
    cfg, params, tokens = f32
    fn = _interpreted if attention == "flash_interpreted" else None
    logits, state = cfg.module().apply(
        params, tokens, attention_fn=fn, mutable=["intermediates"])
    want, want_counts = ref.forward(cfg, ref.published(cfg, params), tokens)
    np.testing.assert_allclose(logits, want, atol=3e-6, rtol=0)
    # and the order matters: the reference on the module's own columns
    # (rope taken from nope lanes) is another function
    wrong, _ = ref.forward(cfg, params, tokens)
    assert float(jnp.abs(wrong - want).max()) > 1e-3
    sown = {k: v[0] for k, v in state["intermediates"].items()}
    assert set(sown) == set(cfg.round_stats) == set(cfg.STATS)
    np.testing.assert_array_equal(sown["moe_tokens"], want_counts)
    assert sown["moe_tokens"].shape == (2, cfg.n_routed_experts)
    # every assignment got a row: nothing dropped
    np.testing.assert_array_equal(sown["moe_placed"], sown["moe_held"])
    np.testing.assert_array_equal(sown["moe_held"], [B * T * 2] * 2)
    np.testing.assert_array_equal(sown["moe_tokens"].sum(1), sown["moe_held"])


def _losses(cfg, params, tokens, attention_fn=None):
    targets = jnp.roll(tokens, -1, axis=1)

    def module_loss(lora):
        logits = cfg.module().apply(merge_lora(params, lora), tokens,
                                    attention_fn=attention_fn)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))

    def reference_loss(lora):
        # the permutation is differentiated through: the gradient comes
        # back in the module's own column order
        return ref.loss(cfg, ref.published(cfg, merge_lora(params, lora)),
                        tokens, targets)

    return module_loss, reference_loss


def _assert_adapter_gradients(cfg, params, tokens, attention_fn=None):
    module_loss, reference_loss = _losses(cfg, params, tokens, attention_fn)
    lora = extract_lora(params)
    assert len(lora) == 2 * 5 * cfg.num_hidden_layers
    assert {k.split("/")[-2] for k in lora} == {
        "q_a_proj", "q_b_proj", "kv_a_proj", "kv_b_proj", "o_proj"}
    loss, got = jax.jit(jax.value_and_grad(module_loss))(lora)
    want_loss, want = jax.jit(jax.value_and_grad(reference_loss))(lora)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    for k in lora:
        scale = float(jnp.abs(want[k]).max())
        assert scale > 0, k
        np.testing.assert_allclose(got[k], want[k], rtol=0, atol=1e-4 * scale,
                                   err_msg=k)


def test_every_adapter_leafs_gradient_is_the_references(f32):
    """``jax.grad`` of the module's loss against ``jax.grad`` of the plain
    reference's, for all 30 adapter leaves (five projections a layer, two
    of them on a latent); float32 both, so 1e-4 of the leaf's largest
    entry covers the order of summation."""
    _assert_adapter_gradients(*f32)


def test_a_head_of_256_with_64_rotary_lanes_through_the_flash_kernels():
    """The published head — 192 + 64 score lanes, 256 value lanes — at two
    heads and T 128 with the three flash kernels under the interpreter:
    logits (``atol`` 2e-5: the online softmax's blocks and a 256-lane sum
    in another order) and every adapter's gradient (1e-4 of its leaf's
    scale) against the reference's plain ``[T, T]`` softmax."""
    cfg = _f32(hidden_size=64, num_attention_heads=2, num_key_value_heads=2,
               qk_nope_head_dim=192, qk_rope_head_dim=64, v_head_dim=256,
               q_lora_rank=48, kv_lora_rank=32, num_hidden_layers=2,
               intermediate_size=96, moe_intermediate_size=48)
    params, tokens = seeded(cfg, shape=(1, 128))
    logits = cfg.module().apply(params, tokens, attention_fn=_interpreted)
    want, _ = ref.forward(cfg, ref.published(cfg, params), tokens)
    np.testing.assert_allclose(logits, want, atol=2e-5, rtol=0)
    _assert_adapter_gradients(cfg, params, tokens, _interpreted)


def test_a_module_computing_in_bfloat16_fails_these_tolerances(f32):
    """The tolerances above are tight enough to tell the precision: the
    same weights through a module whose compute type is bfloat16 miss the
    logits by a thousand times ``atol`` and a gradient by more than 1e-4
    of its scale."""
    cfg, params, tokens = f32
    low = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    want, _ = ref.forward(cfg, ref.published(cfg, params), tokens)
    got = low.module().apply(params, tokens)
    assert float(jnp.abs(got - want).max()) > 1e-3
    module_loss, reference_loss = _losses(low, params, tokens)
    lora = extract_lora(params)
    got, want = (jax.jit(jax.grad(f))(lora)
                 for f in (module_loss, reference_loss))
    worst = max(float(jnp.abs(got[k] - want[k]).max()
                      / jnp.abs(want[k]).max()) for k in lora)
    assert worst > 1e-3


def test_only_the_adapters_train_and_travel(f32):
    """The router's leaves are ``router_weight`` and ``router_bias``, not
    the key ``router`` that ``is_trainable_path`` catches: what the
    optimizer sees and what the round exchanges are the same 30 leaves."""
    _, params, _ = f32
    assert set(extract_trainable(params)) == set(extract_lora(params))
    assert not any(part in k for k in extract_trainable(params)
                   for part in ("router", "experts", "shared", "mlp", "norm"))


@pytest.mark.parametrize("t", [0, 7, T - 2])
def test_no_logit_sees_a_later_token(f32, t):
    cfg, params, tokens = f32
    other = tokens.at[:, t + 1:].set((tokens[:, t + 1:] + 3) % cfg.vocab_size)
    a = cfg.module().apply(params, tokens)
    b = cfg.module().apply(params, other)
    np.testing.assert_allclose(a[:, :t + 1], b[:, :t + 1], atol=1e-6, rtol=0)
    assert float(jnp.abs(a[:, t + 1:] - b[:, t + 1:]).max()) > 1e-3


def test_every_head_sees_the_one_rotary_key(f32):
    """What reaches the attention product: ``k``'s leading ``rope`` lanes
    are the same array for all heads — the rotary embedding of the last
    ``rope`` columns of ``u W_kva``, turned once — and ``q``'s differ by
    head; the other lanes are each head's own."""
    cfg, _, _ = f32
    r, lat = cfg.qk_rope_head_dim, cfg.kv_lora_rank
    u = jax.random.normal(jax.random.key(2), (B, T, cfg.hidden_size))
    cos, sin = (f(jnp.arange(T)[:, None] * cfg.rope_theta ** (
        -jnp.arange(r // 2) / (r // 2))) for f in (jnp.cos, jnp.sin))
    seen = {}

    def spy(q, k, v):
        seen.update(q=q, k=k, v=v)
        return jnp.zeros_like(v)

    module = LatentAttention(cfg)
    p = unbox(module.init(jax.random.key(3), u, cos, sin))
    module.apply(p, u, cos, sin, spy)
    q, k, v = seen["q"], seen["k"], seen["v"]
    assert q.shape == k.shape == v.shape == (B, 4, T, 16)
    for head in range(1, 4):
        np.testing.assert_array_equal(k[:, head, :, :r], k[:, 0, :, :r])
        assert float(jnp.abs(k[:, head, :, r:] - k[:, 0, :, r:]).max()) > 0.1
        assert float(jnp.abs(q[:, head, :, :r] - q[:, 0, :, :r]).max()) > 0.1
    with jax.default_matmul_precision("highest"):
        k_r = (u @ p["params"]["kv_a_proj"]["kernel"])[..., lat:]
    want = jax.vmap(lambda x: ref.rotary(cfg, x))(k_r)
    np.testing.assert_allclose(k[:, 0, :, :r], want, atol=1e-5, rtol=0)


def _moe_layer(cfg, seed=4):
    u = jax.random.normal(jax.random.key(seed), (B, T, cfg.hidden_size))
    p = unbox(GlmMoeLiteMoE(cfg).init(jax.random.key(seed + 1), u))["params"]
    return u, p


def _module_and_reference(cfg, u, p):
    out, stats = GlmMoeLiteMoE(cfg).apply({"params": p}, u)
    with jax.default_matmul_precision("highest"):
        want, counts = ref.moe(cfg, u.reshape(B * T, -1), p)
    np.testing.assert_allclose(out.reshape(B * T, -1), want, atol=2e-5,
                               rtol=0)
    np.testing.assert_array_equal(stats["moe_tokens"], counts)
    assert int(stats["moe_placed"]) == int(stats["moe_held"]) == B * T * 2
    return out, stats


def test_the_selection_bias_picks_and_does_not_weigh():
    """A router that scores every expert 0.5 for every token (a tie all
    over): the bias alone decides, so every token takes the two experts it
    favours, and each of the two weighs ``routed_scaling_factor / 2``
    whatever the bias's size — the output is ``0.9 x`` the sum of those two
    experts' outputs plus the shared expert's. Without a bias the tie goes
    to the lowest indices, in the module and in the reference alike."""
    cfg = _f32()
    u, p = _moe_layer(cfg)
    p["router_weight"] = jnp.zeros_like(p["router_weight"])
    _, stats = _module_and_reference(cfg, u, p)
    np.testing.assert_array_equal(
        stats["moe_tokens"], [B * T, B * T, 0, 0, 0, 0, 0, 0])
    p["router_bias"] = jnp.zeros((8,)).at[5].set(0.3).at[2].set(7.0)
    out, stats = _module_and_reference(cfg, u, p)
    np.testing.assert_array_equal(
        stats["moe_tokens"], [0, 0, B * T, 0, 0, B * T, 0, 0])
    flat = u.reshape(B * T, -1)
    with jax.default_matmul_precision("highest"):
        one = lambda e: (jax.nn.silu(flat @ p["experts"]["gate_proj"][e])
                         * (flat @ p["experts"]["up_proj"][e])) \
            @ p["experts"]["down_proj"][e]
        want = 0.9 * (one(2) + one(5)) + ref.swiglu(flat, p["shared"])
    np.testing.assert_allclose(out.reshape(B * T, -1), want, atol=2e-5,
                               rtol=0)


def test_no_assignment_is_dropped_when_every_token_takes_one_expert():
    """A bias that puts expert 3 first for every token: its run is all
    ``B x T`` tokens (five whole tiles of 8), the second choices spread by
    the scores, and every one of the ``2 B T`` assignments holds a row."""
    cfg = _f32()
    u, p = _moe_layer(cfg, seed=6)
    p["router_weight"] = 3.0 * p["router_weight"]
    p["router_bias"] = jnp.zeros((8,)).at[3].set(5.0)
    _, stats = _module_and_reference(cfg, u, p)
    counts = np.asarray(stats["moe_tokens"])
    assert counts[3] == B * T and counts.sum() == 2 * B * T
    assert int(stats["moe_live"]) == int((counts > 0).sum()) > 2
    assert cfg.moe_capacity_rows(B * T) >= 2 * B * T + 8 * 7 - 7


@pytest.mark.parametrize("dense,kinds", [(0, "EEE"), (1, "DEE"), (2, "DDE"),
                                         (3, "DDD")])
def test_the_leading_layers_are_dense_and_the_rest_expert(dense, kinds):
    cfg = GlmMoeLiteConfig.tiny(first_k_dense_replace=dense)
    tokens = jnp.zeros((1, 8), jnp.int32)
    shapes = unbox(jax.eval_shape(cfg.module().init, jax.random.key(0),
                                  tokens))["params"]
    for i, kind in enumerate(kinds):
        layer = shapes[f"layer_{i}"]
        assert ("mlp" in layer, "moe" in layer) == (kind == "D", kind == "E")
        assert set(layer["attn"]) == {
            "q_a_proj", "q_a_norm", "q_b_proj", "kv_a_proj", "kv_a_norm",
            "kv_b_proj", "o_proj"}
        assert cfg.module().layer_block(i).dense == (kind == "D")
    assert cfg.expert_layers == kinds.count("E")
    assert cfg.round_stats == (cfg.STATS if "E" in kinds else ())
    _, state = jax.eval_shape(
        lambda p: cfg.module().apply({"params": p}, tokens,
                                     mutable=["intermediates"]), shapes)
    sown = state.get("intermediates", {})
    if "E" in kinds:
        assert sown["moe_tokens"][0].shape == (kinds.count("E"), 8)
    else:
        assert not sown


def test_the_yaml_names_the_model():
    class Args:
        model, model_size, lora_rank = "glm4_moe_lite", "tiny", 4
        first_k_dense_replace, num_hidden_layers = 2, 4

    cfg = config_from_args(Args(), vocab_size=99)
    assert isinstance(cfg, GlmMoeLiteConfig) and cfg.vocab_size == 99
    assert (cfg.first_k_dense_replace, cfg.num_hidden_layers,
            cfg.lora_rank) == (2, 4, 4)
    assert type(cfg.module()).__name__ == "GlmMoeLiteForCausalLM"
    assert [cfg.module().layer_block(i).dense for i in range(4)] == [
        True, True, False, False]
    from fedml_tpu.models import model_hub

    assert type(model_hub.create(Args(), 64)).__name__ == \
        "GlmMoeLiteForCausalLM"


def test_a_cache_is_refused(f32):
    cfg, params, tokens = f32
    with pytest.raises(NotImplementedError, match="serving"):
        cfg.module().apply(params, tokens, kv_caches=[()] * 3)


def _api(on_device: bool):
    from tests.test_nemotron_h import _api as tiny_api

    return tiny_api(on_device, model="glm4_moe_lite")


def test_every_grouped_product_of_a_round_leaves_its_plan(monkeypatch):
    """Tracing the tiny fused round with the grouped products as KERNELS
    (the interpreter's form, steered here) leaves one ``moe_gmm/plan`` a
    product and direction: three products an expert layer each way, none
    with an activation (the SwiGLU takes two products' outputs and stays
    outside) — and one ``mla/plan`` a layer."""
    monkeypatch.setattr(gmm, "kernel_mode", lambda *a, **kw: gmm.INTERPRET)
    api = _api(on_device=True)
    engine, cfg = api.client.engine, api.cfg
    feed = jax.ShapeDtypeStruct((2, 2, engine.batch_size, engine.seq_len),
                                np.int32)
    telemetry.reset_tracer()
    engine.compile_federated_round(2, 2).lower(
        engine.params, engine.opt_state, api.global_exchange, feed, feed,
        jax.ShapeDtypeStruct(feed.shape[:3], np.float32),
        jax.ShapeDtypeStruct(feed.shape[:1], np.float32))
    records = telemetry.get_tracer().records()
    plans = [r["attrs"] for r in records if r["name"] == "moe_gmm/plan"]
    rows = cfg.moe_capacity_rows(engine.batch_size * engine.seq_len)
    assert len(plans) == 6 * cfg.expert_layers
    assert all(p["rows"] == rows and p["block_m"] == cfg.moe_block_rows
               and p["activation"] is None and p["form"] == "interpret"
               for p in plans)
    hid, mid = cfg.hidden_size, cfg.moe_intermediate_size
    assert sorted((p["transpose"], p["k"], p["n"]) for p in plans) == sorted(
        ([(False, hid, mid)] * 2 + [(False, mid, hid)]
         + [(True, mid, hid)] * 2 + [(True, hid, mid)]) * cfg.expert_layers)
    mla = [r for r in records if r["name"] == "mla/plan"]
    assert len(mla) >= cfg.num_hidden_layers and all(r["point"] for r in mla)
    assert mla[0]["attrs"] == {
        "rows": engine.batch_size * engine.seq_len, "heads": 4,
        "q_latent": 24, "kv_latent": 16, "nope": 12, "rope": 4, "v_dim": 16,
        "lane_order": "rope|nope", "dtype": "bfloat16"}
    rope = [r["attrs"] for r in records if r["name"] == "rope/plan"]
    # q: whole heads of 16 with 4 rotary lanes; the one key: a head of 4
    assert {(p["heads"], p["head_dim"], p["rotary_dim"]) for p in rope} == {
        (4, 16, 4), (1, 4, 4)}
    telemetry.reset_tracer()


def test_the_fused_round_of_a_tiny_glm_is_the_host_loops():
    """``fedml_tpu.init`` -> ``FedLLMAPI(on_device_round: true)`` ->
    ``train_one_round``: the same ``compile_federated_round`` as the other
    families', whose fifth output becomes the ``round/<n>/moe`` event (the
    dense layer counts nothing); and that program against the host loop it
    replaces, from the same state on the same rows (bfloat16 compute on
    both sides: 5e-3 of an adapter's largest entry covers XLA's freedom to
    fuse the two programs differently)."""
    from fedml_tpu.ml.aggregator.agg_operator import FedMLAggOperator
    from fedml_tpu.telemetry.profiling import get_catalog

    telemetry.reset_tracer()
    api = _api(on_device=True)
    assert isinstance(api.cfg, GlmMoeLiteConfig)
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
            attrs["top_k"]) == (2, 8, 8, 2)
    assert attrs["tokens"] == tokens and attrs["steps"] == 2 * 2
    assert attrs["assignments"] == tokens * 2
    assert attrs["held_share"] == 1.0
    assert attrs["capacity_rows"] == cfg.moe_capacity_rows(
        engine.batch_size * engine.seq_len)
    assert 1.0 <= attrs["max_over_mean"] <= cfg.n_routed_experts
    assert 0.0 < attrs["live_share"] <= 1.0
    assert 1.0 <= attrs["tiles_per_run"] \
        <= attrs["capacity_rows"] / cfg.moe_block_rows
    assert any(r["name"] == "mla/plan" for r in records)
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
    assert len(host) == 30
    for k, v in host.items():
        scale = float(jnp.abs(v).max())
        np.testing.assert_allclose(api.global_exchange[k], v, rtol=0,
                                   atol=5e-3 * scale, err_msg=k)
        assert float(jnp.abs(v - g0[k]).max()) > 0, k  # and it moved
    telemetry.reset_tracer()
