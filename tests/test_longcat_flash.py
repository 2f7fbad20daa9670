"""``model: longcat_flash`` against its plain float32 reference, on the CPU
at tiny widths with the published row's shape (double layers of two latent
attentions with value heads narrower than the score lanes, two dense FFNs
and the shortcut expert branch; top-3 of 8 FFN experts and 4 identity
experts; an untied head), on seeded random weights, with plain attention
and with the flash kernels under the interpreter; the branch's topology,
the router's corner cases and the held shares; then through ``FedLLMAPI``'s
fused round. A timing here is never a speed."""
import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu import telemetry
from fedml_tpu.models.llm import config_from_args
from fedml_tpu.models.llm import longcat_flash_reference as ref
from fedml_tpu.models.llm.layers import softmax_topk
from fedml_tpu.models.llm.longcat_flash import (
    LongcatFlashBlock,
    LongcatFlashConfig,
    LongcatFlashMoE,
)
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
    spread over more than rounding. The selection bias is small beside the
    probabilities' spread over 12 outputs, so that it moves choices at
    near-ties and does not pick alone."""
    tokens = jax.random.randint(jax.random.key(seed + 1), shape, 0,
                                cfg.vocab_size)
    params = unbox(jax.jit(cfg.module().init)(jax.random.key(seed), tokens))
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for i, (path, v) in enumerate(flat):
        name, key = _path(path), jax.random.fold_in(jax.random.key(seed + 7), i)
        if "lora_b" in name:
            v = 0.05 * jax.random.normal(key, v.shape, v.dtype)
        elif name.endswith("router_bias"):
            v = 0.01 * jax.random.normal(key, v.shape, v.dtype)
        elif name.endswith("scale"):
            v = v + 0.3 * jax.random.normal(key, v.shape, v.dtype)
        elif name.endswith("router_weight"):
            v = 3.0 * v
        out.append(v)
    return jax.tree_util.tree_unflatten(treedef, out), tokens


def _f32(**kw):
    return LongcatFlashConfig.tiny(lora_rank=4, dtype=jnp.float32,
                                   param_dtype=jnp.float32, **kw)


@pytest.fixture(scope="module")
def f32():
    cfg = _f32()
    params, tokens = seeded(cfg)
    return cfg, params, tokens


def _interpreted(q, k, v):
    return flash_attention(q, k, v, causal=True, interpret=True)


def test_the_tiny_preset_keeps_the_rows_shape_and_ratios():
    tiny, row = LongcatFlashConfig.tiny(), \
        LongcatFlashConfig.longcat_flash_chat()
    assert (row.num_layers, row.num_hidden_layers) == (28, 28)
    assert (row.head_dim, row.rotary_dim, row.v_head_dim) == (192, 64, 128)
    assert (row.q_lora_rank, row.kv_lora_rank) == (1536, 512)
    assert (row.experts_total, row.zero_expert_num, row.moe_topk) == (
        512, 256, 12)
    assert row.mla_scales == pytest.approx((2.0, 12 ** 0.5))
    assert row.moe_static == {"experts": 768, "top_k": 12}
    for cfg in (tiny, row):
        assert 3 * cfg.rotary_dim == cfg.head_dim == 3 * cfg.v_head_dim // 2
        assert cfg.q_lora_rank == 3 * cfg.kv_lora_rank
        assert cfg.ffn_hidden_size == 2 * cfg.hidden_size
        assert 2 * cfg.zero_expert_num == cfg.experts_total
        assert cfg.moe_intermediate_size == cfg.expert_ffn_hidden_size
    for key in ("routed_scaling_factor", "zero_expert_type", "rope_theta",
                "tie_word_embeddings", "attention_method", "rms_norm_eps",
                "mla_scale_q_lora", "mla_scale_kv_lora"):
        assert getattr(tiny, key) == getattr(row, key), key


@pytest.mark.parametrize("bad", [
    {"tie_word_embeddings": True}, {"attention_bias": True},
    {"attention_method": "GQA"}, {"zero_expert_type": "constant"},
    {"qk_rope_head_dim": 3}, {"moe_topk": 13}, {"mla_scale_kv_lora": False},
    {"held_experts_first": 4}, {"n_routed_experts_total": 4}])
def test_what_is_not_implemented_is_refused(bad):
    with pytest.raises(ValueError, match="not implemented"):
        LongcatFlashConfig.tiny(**bad)


def test_the_published_lane_order_is_a_permutation_of_two_matrices(f32):
    """``ref.published`` moves ``q_b_proj``'s columns a head from ``[rope
    (halves paired) | nope]`` to ``[nope | rope (neighbours paired)]`` and
    ``kv_a_proj``'s rope columns from halves to neighbours; nothing else."""
    cfg, params, _ = f32
    moved = ref.published(cfg, params)["params"]["layer_0"]
    here = params["params"]["layer_0"]
    q, q_here = (t["attn_1"]["q_b_proj"]["kernel"] for t in (moved, here))
    d, rope = cfg.head_dim, cfg.qk_rope_head_dim
    # head 1's first published lane is its first nope lane (after the 4
    # rope lanes here); its rope lanes come as (0, 2), (1, 3)
    np.testing.assert_array_equal(q[:, d], q_here[:, d + rope])
    np.testing.assert_array_equal(
        q[:, d + d - rope:2 * d], q_here[:, d + np.array([0, 2, 1, 3])])
    kv, kv_here = (t["attn_0"]["kv_a_proj"]["lora_b"] for t in (moved, here))
    lat = cfg.kv_lora_rank
    np.testing.assert_array_equal(kv[:, :lat], kv_here[:, :lat])
    np.testing.assert_array_equal(kv[:, lat:],
                                  kv_here[:, lat + np.array([0, 2, 1, 3])])
    np.testing.assert_array_equal(moved["mlp_0"]["up_proj"]["kernel"],
                                  here["mlp_0"]["up_proj"]["kernel"])


# -- the model against the reference -----------------------------------------
@pytest.mark.parametrize("attention", ["plain", "flash_interpreted"])
def test_logits_loss_and_counts_are_the_references(f32, attention):
    """float32 on both sides, the reference in the PUBLISHED lane order
    (``ref.published`` moves ``q_b_proj``'s and ``kv_a_proj``'s columns):
    what is left is the order of summation (3e-7 of logits of order 0.5) —
    the sorted grouped product and the weighted combine against the loop
    with a mask, one fused rope pass against the split form, and under the
    interpreter the online softmax in blocks at 12 score and 8 value
    lanes."""
    cfg, params, tokens = f32
    fn = _interpreted if attention == "flash_interpreted" else None
    logits, state = cfg.module().apply(
        params, tokens, attention_fn=fn, mutable=["intermediates"])
    want, want_counts, want_zero = ref.forward(
        cfg, ref.published(cfg, params), tokens)
    np.testing.assert_allclose(logits, want, atol=3e-6, rtol=0)
    # and the order matters: the reference on the module's own columns is
    # another function
    wrong, _, _ = ref.forward(cfg, params, tokens)
    assert float(jnp.abs(wrong - want).max()) > 1e-3
    sown = {k: v[0] for k, v in state["intermediates"].items()}
    assert set(sown) == set(cfg.round_stats) == set(cfg.STATS)
    np.testing.assert_array_equal(sown["moe_tokens"], want_counts)
    np.testing.assert_array_equal(sown["moe_zero"], want_zero)
    assert sown["moe_tokens"].shape == (cfg.num_layers, cfg.n_routed_experts)
    # every held assignment got a row; identity choices got none
    np.testing.assert_array_equal(sown["moe_placed"], sown["moe_held"])
    np.testing.assert_array_equal(sown["moe_tokens"].sum(1), sown["moe_held"])
    np.testing.assert_array_equal(sown["moe_held"] + sown["moe_zero"],
                                  B * T * cfg.moe_topk)
    assert int(sown["moe_zero"].min()) > 0


def _losses(cfg, params, tokens, attention_fn=None):
    targets = jnp.roll(tokens, -1, axis=1)

    def module_loss(lora):
        logits = cfg.module().apply(merge_lora(params, lora), tokens,
                                    attention_fn=attention_fn)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))

    def reference_loss(lora):
        return ref.loss(cfg, ref.published(cfg, merge_lora(params, lora)),
                        tokens, targets)

    return module_loss, reference_loss


@pytest.mark.parametrize("attention", ["plain", "flash_interpreted"])
def test_every_adapter_leafs_gradient_is_the_references(f32, attention):
    """``jax.grad`` of the module's loss against ``jax.grad`` of the plain
    reference's (its columns moved inside the loss, so both gradients are
    in the module's order), for all 40 adapter leaves of two double
    layers; float32 both, so 1e-4 of the leaf's largest entry covers the
    order of summation — through the weighted combine's own transpose, the
    grouped product's and the flash kernels' at a narrower value head."""
    cfg, params, tokens = f32
    fn = _interpreted if attention == "flash_interpreted" else None
    module_loss, reference_loss = _losses(cfg, params, tokens, fn)
    lora = extract_lora(params)
    assert len(lora) == 2 * 2 * 5 * 2
    assert {k.split("/")[-3] for k in lora} == {"attn_0", "attn_1"}
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
    logits by a hundred times ``atol`` and a gradient by more than 1e-4 of
    its scale."""
    cfg, params, tokens = f32
    low = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    want, _, _ = ref.forward(cfg, ref.published(cfg, params), tokens)
    got = low.module().apply(params, tokens)
    assert float(jnp.abs(got - want).max()) > 3e-4
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
    optimizer sees and what the round exchanges are the same 40 leaves."""
    _, params, _ = f32
    assert set(extract_trainable(params)) == set(extract_lora(params))
    assert not any(part in k for k in extract_trainable(params)
                   for part in ("router", "experts", "mlp", "norm"))


@pytest.mark.parametrize("t", [0, 7, T - 2])
def test_no_logit_sees_a_later_token(f32, t):
    cfg, params, tokens = f32
    other = tokens.at[:, t + 1:].set((tokens[:, t + 1:] + 3) % cfg.vocab_size)
    a = cfg.module().apply(params, tokens)
    b = cfg.module().apply(params, other)
    np.testing.assert_allclose(a[:, :t + 1], b[:, :t + 1], atol=1e-6, rtol=0)
    assert float(jnp.abs(a[:, t + 1:] - b[:, t + 1:]).max()) > 1e-4


# -- the double layer's topology ----------------------------------------------
def test_the_branch_reads_the_first_ffns_input_and_joins_after_the_second(
        f32):
    """One double layer with every submodule's output captured: the expert
    branch's output is the branch applied to ``u_0`` (the first FFN's normed
    input) and not to ``u_1``; the second attention reads a stream without
    the branch (it has not joined yet); the layer's output is its input
    plus both attentions, both FFNs and the branch, each once."""
    cfg, params, _ = f32
    layer = params["params"]["layer_0"]
    x = jax.random.normal(jax.random.key(5), (B, T, cfg.hidden_size))
    t = jnp.arange(T)
    from fedml_tpu.models.llm.layers import rope_tables

    cos, sin = rope_tables(t, cfg.rotary_dim, cfg.rope_theta)
    (out, _, _, _), state = LongcatFlashBlock(cfg).apply(
        {"params": layer}, x, None, cos, sin,
        capture_intermediates=True, mutable=["intermediates"])
    got = {k: v["__call__"][0] for k, v in state["intermediates"].items()
           if k != "__call__"}
    u0, u1 = got["post_attn_norm_0"], got["post_attn_norm_1"]
    m, _ = got["moe"]
    branch = lambda u: LongcatFlashMoE(cfg).apply({"params": layer["moe"]},
                                                 u)[0]
    np.testing.assert_allclose(m, branch(u0), atol=1e-6, rtol=0)
    assert float(jnp.abs(branch(u1) - m).max()) > 1e-2
    before_attn_1 = x + got["attn_0"] + got["mlp_0"]
    norm = lambda y, name: y * jax.lax.rsqrt(
        jnp.mean(y * y, -1, keepdims=True) + cfg.rms_norm_eps) \
        * layer[name]["scale"]
    np.testing.assert_allclose(got["input_norm_1"],
                               norm(before_attn_1, "input_norm_1"),
                               atol=1e-5, rtol=0)
    assert float(jnp.abs(got["input_norm_1"] - norm(
        before_attn_1 + m, "input_norm_1")).max()) > 1e-2
    np.testing.assert_allclose(
        out, before_attn_1 + got["attn_1"] + got["mlp_1"] + m, atol=1e-5,
        rtol=0)


# -- the router ----------------------------------------------------------------
class _Router(nn.Module):
    total: int
    k: int

    @nn.compact
    def __call__(self, u):
        return softmax_topk(self, u, self.total, self.k, bias=True,
                            renormalize=False, scale=6.0)


def test_the_selection_bias_picks_and_does_not_weigh():
    """Logits 2, 1, 1, 0, 0, 0 over six outputs, top-2: without a bias the
    tie between outputs 1 and 2 goes to 1; a bias of 0.5 on output 2 picks
    it (and output 0 stays chosen). The weights are 6 x the softmax's
    probabilities of the chosen — not re-normalised over them, and with no
    bias in them."""
    u = jnp.ones((1, 1))
    logits = jnp.array([[2.0, 1.0, 1.0, 0.0, 0.0, 0.0]])
    p = jax.nn.softmax(logits, axis=-1)[0]
    router = _Router(6, 2)
    for bias, picked in ((jnp.zeros(6), (0, 1)),
                         (jnp.zeros(6).at[2].set(0.5), (0, 2))):
        chosen, weights = router.apply(
            {"params": {"router_weight": logits, "router_bias": bias}}, u)
        assert tuple(sorted(chosen[0].tolist())) == picked
        want = jnp.zeros(6).at[jnp.array(picked)].set(6.0 * p[jnp.array(
            picked)])
        np.testing.assert_allclose(weights[0], want, rtol=1e-6)
        assert float(weights.sum()) < 6.0 * 0.99


def test_the_qwen3_next_router_still_renormalises():
    """``softmax_topk``'s defaults are ``qwen3_next``'s: no bias leaf, the
    chosen probabilities over their sum, no scale."""

    class Plain(nn.Module):
        @nn.compact
        def __call__(self, u):
            return softmax_topk(self, u, 6, 2)

    u = jnp.ones((1, 1))
    logits = jnp.array([[2.0, 1.0, 0.5, 0.0, 0.0, 0.0]])
    variables = {"params": {"router_weight": logits}}
    assert set(Plain().init(jax.random.key(0), u)["params"]) == {
        "router_weight"}
    chosen, weights = Plain().apply(variables, u)
    p = jax.nn.softmax(logits, axis=-1)[0]
    assert sorted(chosen[0].tolist()) == [0, 1]
    np.testing.assert_allclose(weights[0, :2], p[:2] / p[:2].sum(),
                               rtol=1e-6)


def test_identity_choices_take_no_row_and_add_their_weight_times_u():
    """Every token's three choices fixed by the router's logits: output 1
    (an FFN expert, held) and outputs 9, 10 (identity experts, past the 8
    FFN experts). The layout places one row a token, for expert 1 alone;
    the branch is that expert's product weighed by its probability, plus
    the identity experts' weights times ``u``."""
    cfg = _f32()
    u = jax.random.normal(jax.random.key(1), (1, 6, cfg.hidden_size))
    moe = LongcatFlashMoE(cfg)
    p = unbox(moe.init(jax.random.key(2), u))["params"]
    outputs = cfg.experts_total + cfg.zero_expert_num
    logit = jnp.zeros((outputs,)).at[jnp.array([1, 9, 10])].set(30.0)
    # a constant input direction: every token's logits are ``logit``
    u = jnp.ones_like(u)
    p["router_weight"] = jnp.tile(logit / cfg.hidden_size,
                                  (cfg.hidden_size, 1))
    out, stats = moe.apply({"params": p}, u)
    assert int(stats["moe_held"]) == int(stats["moe_placed"]) == 6
    assert int(stats["moe_zero"]) == 12
    assert stats["moe_tokens"].tolist() == [0, 6] + [0] * 6
    probs = jax.nn.softmax(logit)
    ex = p["experts"]
    flat = u.reshape(6, -1)
    expert = (jax.nn.silu(flat @ ex["gate_proj"][1])
              * (flat @ ex["up_proj"][1])) @ ex["down_proj"][1]
    want = 6.0 * (probs[1] * expert + (probs[9] + probs[10]) * flat)
    np.testing.assert_allclose(out.reshape(6, -1), want, atol=1e-5,
                               rtol=1e-5)
    layout = gmm.group_layout(jnp.tile(jnp.array([[1, 9, 10]]), (6, 1)),
                              cfg.n_routed_experts, cfg.moe_block_rows)
    assert layout.held.tolist() == [[True, False, False]] * 6
    assert int(layout.valid.sum()) == 6


def test_the_weighted_combine_is_combine_then_the_weighted_sum():
    """``gmm.combine_weighted`` against :func:`gmm.combine` followed by the
    float32 weighted sum over a token's choices: values and both
    gradients (the rows' and the weights'), with choices that are not held
    adding nothing and padding rows getting an exact zero."""
    m, k, held, n = 12, 3, 4, 8
    chosen = jax.random.permutation(
        jax.random.key(0), jnp.tile(jnp.arange(10), (m, 1)), axis=1,
        independent=True)[:, :k]
    layout = gmm.group_layout(chosen, held, 8, first=2)
    rows = layout.src.shape[0]
    y = jax.random.normal(jax.random.key(1), (rows, n))
    w = jax.random.uniform(jax.random.key(2), (m, k))
    g = jax.random.normal(jax.random.key(3), (m, n))

    def two_steps(y, w):
        mine = gmm.combine(y, layout).astype(jnp.float32)
        return jnp.sum(mine * jnp.where(layout.held, w, 0.0)[..., None], 1)

    def one_step(y, w):
        return gmm.combine_weighted(y, layout, w)

    np.testing.assert_allclose(one_step(y, w), two_steps(y, w), atol=1e-6)
    got = jax.grad(lambda y, w: jnp.sum(one_step(y, w) * g), (0, 1))(y, w)
    want = jax.grad(lambda y, w: jnp.sum(two_steps(y, w) * g), (0, 1))(y, w)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-6)
    assert float(jnp.abs(got[0][~layout.valid]).max()) == 0.0
    assert 0 < int(layout.held.sum()) < m * k


# -- the held shares -------------------------------------------------------------
def test_the_held_shares_add_up_to_the_uncut_layer(f32):
    """The share test: one branch's router over 32 FFN experts and 4
    identity experts; the 32 chips of the deployment hold one FFN expert
    each. What each share's module gives for its held range, less the
    identity experts' part (which every chip computes alike for its own
    tokens, so it is counted once), added over the 32 ranges equals the
    reference's uncut branch (all 32 held)."""
    cfg, _, _ = f32
    whole = _f32(n_routed_experts=32)
    u = jax.random.normal(jax.random.key(3), (B, T, cfg.hidden_size))
    p = unbox(LongcatFlashMoE(whole).init(jax.random.key(4), u))["params"]
    p["router_weight"] = 3.0 * p["router_weight"]
    p["router_bias"] = 0.01 * jax.random.normal(jax.random.key(6),
                                                p["router_bias"].shape)
    flat_u = u.reshape(B * T, -1)
    with jax.default_matmul_precision("highest"):
        want, counts, zero = ref.moe(whole, flat_u, p)
        none, _ = ref.identity(whole, flat_u, *ref.route(whole, flat_u, p))
    assert int(counts.sum()) + int(zero) == B * T * whole.moe_topk
    assert 0 < int(zero) < B * T * whole.moe_topk
    total, held = jnp.zeros_like(want), 0
    for first in range(32):
        share = _f32(n_routed_experts=1, n_routed_experts_total=32,
                     held_experts_first=first)
        mine = dict(p, experts={k: v[first:first + 1]
                                for k, v in p["experts"].items()})
        out, stats = LongcatFlashMoE(share).apply({"params": mine}, u)
        assert stats["moe_tokens"].tolist() == [int(counts[first])]
        assert int(stats["moe_placed"]) == int(stats["moe_held"])
        assert int(stats["moe_zero"]) == int(zero)
        total = total + out.reshape(B * T, -1) - none
        held += int(stats["moe_held"])
        # and the reference's own share is the module's
        with jax.default_matmul_precision("highest"):
            ref_share, _, _ = ref.moe(share, flat_u, mine)
        np.testing.assert_allclose(out.reshape(B * T, -1), ref_share,
                                   atol=2e-5, rtol=0)
    assert held == int(counts.sum())
    np.testing.assert_allclose(total + none, want, atol=1e-4, rtol=0)


# -- the yaml, the cache, the fused round ---------------------------------------
def test_the_yaml_names_the_model():
    class Args:
        model, model_size, lora_rank = "longcat_flash", "tiny", 4
        n_routed_experts, n_routed_experts_total, held_experts_first = \
            2, 8, 6

    cfg = config_from_args(Args(), vocab_size=99)
    assert isinstance(cfg, LongcatFlashConfig) and cfg.vocab_size == 99
    assert (cfg.n_routed_experts, cfg.held_experts_first, cfg.experts_total,
            cfg.lora_rank) == (2, 6, 8, 4)
    assert type(cfg.module()).__name__ == "LongcatFlashForCausalLM"

    class Row:
        model, model_size, lora_rank = ("longcat_flash",
                                        "LongCat-Flash-Chat", 16)

    assert config_from_args(Row()) == LongcatFlashConfig(lora_rank=16)
    from fedml_tpu.models import model_hub

    assert type(model_hub.create(Args(), 64)).__name__ == \
        "LongcatFlashForCausalLM"


def test_a_cache_is_refused(f32):
    cfg, params, tokens = f32
    with pytest.raises(NotImplementedError, match="serving"):
        cfg.module().apply(params, tokens, kv_caches=[()] * cfg.num_layers)


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
        "model_args": {"model": "longcat_flash", "model_size": "tiny",
                       "lora_rank": 4, "use_flash_attention": False,
                       "n_routed_experts": 2, "n_routed_experts_total": 8,
                       "held_experts_first": 2},
        "train_args": {"federated_optimizer": "FedAvg",
                       "client_num_in_total": 4, "client_num_per_round": 2,
                       "comm_round": never, "frequency_of_the_test": never,
                       "local_steps_per_round": 2, "epochs": 2,
                       "per_device_batch_size": 1, "learning_rate": 5e-3,
                       "on_device_round": on_device},
    }))
    return FedLLMAPI(args, None, load_federated(args), mesh=None)


def test_the_fused_round_of_a_tiny_longcat_flash_is_the_host_loops(
        monkeypatch):
    """``fedml_tpu.init`` -> ``FedLLMAPI(on_device_round: true)`` ->
    ``train_one_round``: the same ``compile_federated_round`` as the other
    families', whose fifth output becomes the ``round/<n>/moe`` event with
    the held range's counts, the identity experts' share and the sorted
    buffer's live share; the attentions leave ``mla/plan`` with both
    scales; and that program against the host loop it replaces, from the
    same state on the same rows. In float32 compute (the tiny preset's,
    steered here): 1e-4 of an adapter's largest entry covers the two
    programs' orders of summation; the bfloat16 round is held to the
    reference by the benchmark's own test
    (``benchmarks/tests/test_longcat_flash_family.py``)."""
    from fedml_tpu.ml.aggregator.agg_operator import FedMLAggOperator
    from fedml_tpu.telemetry.profiling import get_catalog

    tiny = LongcatFlashConfig.tiny
    monkeypatch.setattr(LongcatFlashConfig, "tiny", staticmethod(
        lambda **kw: tiny(**{"dtype": jnp.float32, **kw})))
    telemetry.reset_tracer()
    api = _api(on_device=True)
    assert isinstance(api.cfg, LongcatFlashConfig)
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
            attrs["top_k"]) == (2, 12, 2, 3)
    assert attrs["tokens"] == tokens and attrs["steps"] == 2 * 2
    assert attrs["assignments"] == tokens * 3
    assert 0.0 < attrs["held_share"] < 1.0
    assert 0.0 < attrs["zero_share"] < 1.0
    assert attrs["held_share"] + attrs["zero_share"] < 1.0
    rows = cfg.moe_capacity_rows(engine.batch_size * engine.seq_len)
    assert attrs["capacity_rows"] == rows
    assert attrs["buffer_live_share"] == pytest.approx(
        attrs["held_share"] * tokens * 3 / (4 * rows))
    plans = [r["attrs"] for r in records if r["name"] == "mla/plan"]
    assert plans and all(
        (p["heads"], p["nope"], p["rope"], p["v_dim"], p["lane_order"])
        == (4, 8, 4, 8, "rope|nope") for p in plans)
    assert plans[0]["q_scale"] == pytest.approx(cfg.mla_scales[0])
    assert plans[0]["kv_scale"] == pytest.approx(cfg.mla_scales[1])

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
    assert len(host) == 40
    for k, v in host.items():
        scale = float(jnp.abs(v).max())
        np.testing.assert_allclose(api.global_exchange[k], v, rtol=0,
                                   atol=1e-4 * scale, err_msg=k)
        assert float(jnp.abs(v - g0[k]).max()) > 0, k  # and it moved
    telemetry.reset_tracer()
