"""``model: zaya`` against its plain float32 reference, on the CPU at tiny
widths that keep every ratio of the published row (8/2 heads, latent =
hidden / 2, 16 experts top-1, two taps and two, rope on half of a head,
tied head), on seeded random weights; then through ``FedLLMAPI``'s fused
round. A timing here is never a speed."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu import telemetry
from fedml_tpu.models.llm import config_from_args, zaya_reference
from fedml_tpu.models.llm.zaya import ZayaConfig
from fedml_tpu.ops import grouped_matmul as gmm
from fedml_tpu.train.llm.sharding import unbox
from fedml_tpu.train.llm.trainer import (extract_lora, extract_trainable,
                                         merge_lora)

B, T = 2, 24


def _path(path) -> str:
    return "/".join(str(getattr(p, "key", p)) for p in path)


def seeded(cfg, seed=0, herd=None):
    """``init``'s weights with every leaf that starts at 0 or 1 made
    random (``lora_b``, biases, temperatures, gammas), and the router's
    matrices scaled up so that its logits spread over more than rounding.
    ``herd``: a bias on layer 0's router logits through ``w3`` that sends
    about half of the tokens to that expert."""
    tokens = jax.random.randint(jax.random.key(seed + 1), (B, T), 0,
                                cfg.vocab_size)
    params = unbox(jax.jit(cfg.module().init)(jax.random.key(seed), tokens))
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for i, (path, v) in enumerate(flat):
        name, key = _path(path), jax.random.fold_in(jax.random.key(seed + 7), i)
        if "lora_b" in name or name.endswith("_bias"):
            v = 0.05 * jax.random.normal(key, v.shape, v.dtype)
        elif name.endswith(("k_temp", "gamma", "scale")):
            v = v + 0.1 * jax.random.normal(key, v.shape, v.dtype)
        elif "router_mlp" in name:
            v = 3.0 * v
        if herd is not None and name.endswith("layer_0/moe/router_mlp/w3"):
            v = v.at[:, herd].add(0.35 * jnp.sign(v[:, herd]).sum())
        out.append(v)
    return jax.tree_util.tree_unflatten(treedef, out), tokens


@pytest.fixture(scope="module")
def f32():
    cfg = ZayaConfig.tiny(lora_rank=4, dtype=jnp.float32,
                          param_dtype=jnp.float32)
    params, tokens = seeded(cfg)
    return cfg, params, tokens


def test_the_tiny_preset_keeps_the_rows_ratios():
    tiny, row = ZayaConfig.tiny(), ZayaConfig.zaya1_8b()
    for cfg in (tiny, row):
        assert cfg.num_attention_heads * cfg.head_dim * 2 == cfg.hidden_size
        assert cfg.moe_intermediate_size == cfg.hidden_size
        assert cfg.router_hidden_size * 8 == cfg.hidden_size
        assert cfg.rotary_dim * 2 == cfg.head_dim
    for key in ("num_attention_heads", "num_key_value_heads", "num_experts",
                "num_experts_per_tok", "cca_time0", "cca_time1",
                "tie_word_embeddings"):
        assert getattr(tiny, key) == getattr(row, key), key


@pytest.mark.parametrize("bad", [
    {"num_experts_per_tok": 2}, {"tie_word_embeddings": False},
    {"attention_bias": True}, {"num_key_value_heads": 1}])
def test_what_is_not_implemented_is_refused(bad):
    with pytest.raises(ValueError, match="not implemented"):
        ZayaConfig.tiny(**bad)


def test_logits_and_loss_are_the_references(f32):
    """float32 on both sides: what is left is the order of summation
    (1e-6 of logits of order 1)."""
    cfg, params, tokens = f32
    logits, state = cfg.module().apply(params, tokens,
                                       mutable=["intermediates"])
    want, want_counts = zaya_reference.forward(cfg, params, tokens)
    np.testing.assert_allclose(logits, want, atol=2e-6, rtol=0)
    (counts,) = state["intermediates"]["moe_tokens"]
    np.testing.assert_array_equal(counts, want_counts)
    assert counts.shape == (cfg.num_hidden_layers, cfg.num_experts)
    # every token reached an expert in every layer: nothing dropped
    np.testing.assert_array_equal(counts.sum(1), B * T)


def test_every_adapter_leafs_gradient_is_the_references(f32):
    """``jax.grad`` of the module's loss against ``jax.grad`` of the plain
    reference's, for all 20 adapter leaves of both layers; float32 both,
    so 1e-4 of the leaf's largest entry covers the order of summation."""
    cfg, params, tokens = f32
    targets = jnp.roll(tokens, -1, axis=1)

    def module_loss(lora):
        logits = cfg.module().apply(merge_lora(params, lora), tokens)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))

    def reference_loss(lora):
        return zaya_reference.loss(cfg, merge_lora(params, lora), tokens,
                                   targets)

    lora = extract_lora(params)
    assert len(lora) == 2 * cfg.num_hidden_layers * 5
    assert {k.split("/")[-2] for k in lora} == {
        "q_proj", "k_proj", "v_proj", "v_prev_proj", "o_proj"}
    loss, got = jax.jit(jax.value_and_grad(module_loss))(lora)
    want_loss, want = jax.jit(jax.value_and_grad(reference_loss))(lora)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    for k in lora:
        scale = float(jnp.abs(want[k]).max())
        assert scale > 0, k
        np.testing.assert_allclose(got[k], want[k], rtol=0, atol=1e-4 * scale,
                                   err_msg=k)


def test_only_the_adapters_train_and_travel(f32):
    """The router MLP is not under a key ``is_trainable_path`` catches:
    what the optimizer sees and what the round exchanges are the same 20
    leaves."""
    _, params, _ = f32
    assert set(extract_trainable(params)) == set(extract_lora(params))
    assert not any("router" in k or "experts" in k
                   for k in extract_trainable(params))


@pytest.mark.parametrize("t", [0, 7, T - 2])
def test_no_logit_sees_a_later_token(f32, t):
    """Changing every token after ``t`` moves no logit at or before ``t``:
    a convolution or a shift that looked ahead would."""
    cfg, params, tokens = f32
    other = tokens.at[:, t + 1:].set((tokens[:, t + 1:] + 3) % cfg.vocab_size)
    a = cfg.module().apply(params, tokens)
    b = cfg.module().apply(params, other)
    np.testing.assert_array_equal(a[:, :t + 1], b[:, :t + 1])
    assert float(jnp.abs(a[:, t + 1:] - b[:, t + 1:]).max()) > 1e-3


@pytest.mark.parametrize("switch", ["value_shift", "carry_state"])
def test_each_mechanism_matters(f32, switch):
    """The reference with the previous token's value, or the state handed
    down the stack, zeroed gives other logits than the module's (which
    agrees with the whole reference to 2e-6)."""
    cfg, params, tokens = f32
    logits = cfg.module().apply(params, tokens)
    without, _ = zaya_reference.forward(cfg, params, tokens,
                                        **{switch: False})
    assert float(jnp.abs(logits - without).max()) > 1e-3


def test_a_herded_router_drops_nothing():
    """Layer 0's router seeded to send about half of the tokens to expert
    5: every token is still computed (the logits are the reference's,
    which has no routing code to drop with)."""
    cfg = ZayaConfig.tiny(lora_rank=4, dtype=jnp.float32,
                          param_dtype=jnp.float32)
    params, tokens = seeded(cfg, seed=3, herd=5)
    logits, state = cfg.module().apply(params, tokens,
                                       mutable=["intermediates"])
    (counts,) = state["intermediates"]["moe_tokens"]
    assert counts[0, 5] >= B * T * 0.4, counts[0]
    np.testing.assert_array_equal(counts.sum(1), B * T)
    want, _ = zaya_reference.forward(cfg, params, tokens)
    np.testing.assert_allclose(logits, want, atol=2e-6, rtol=0)


@pytest.mark.parametrize("sizes", [
    [6, 3, 4, 23, 4], [0, 40, 0, 0, 0], [8, 8, 8, 8, 8], [1, 0, 0, 0, 39],
    [40, 33, 5, 64], [8, 0, 17, 1, 0, 32, 9], [24, 0, 0, 0, 0, 0, 0, 0]],
    ids=["uneven", "one_expert", "whole_tiles", "ends", "long_runs",
         "mixed_runs", "long_dead_tail"])
@pytest.mark.parametrize("interpret", [None, True],
                         ids=["reference", "interpreter"])
def test_grouped_product_and_its_row_gradient(sizes, interpret):
    """``moe_gmm`` (the Pallas kernel under the interpreter, and the XLA
    form the CPU gets) against a gather of each row's own matrix: values,
    and the gradient with respect to the rows (the experts are frozen).
    Three column tiles (48 columns in tiles of 16) in every case, so a
    run's weight block is asked for during the run before it, the first
    run's of a column tile during the last run of the one before:
    ``long_runs`` has runs of 5, 5, 1 and 8 row tiles, ``mixed_runs`` of
    1, 3, 1, 4 and 2 with experts that got nothing between them,
    ``one_expert`` one live run only, ``long_dead_tail`` 3 live tiles
    before 7 dead ones."""
    rng = np.random.default_rng(0)
    e, k, n, bm = len(sizes), 32, 48, 8
    expert = jnp.asarray(rng.permutation(np.repeat(np.arange(e), sizes)),
                         jnp.int32)
    x = jnp.asarray(rng.normal(size=(len(expert), k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(e, k, n)), jnp.float32)

    def routed(x):
        layout = gmm.group_layout(expert, e, bm)
        np.testing.assert_array_equal(layout.counts, sizes)
        out = gmm.grouped_matmul(gmm.dispatch(x, layout), w, layout, bm, 16,
                                 interpret=interpret)
        return gmm.combine(out, layout)

    plain = lambda x: jnp.einsum("mk,mkn->mn", x, w[expert])
    np.testing.assert_allclose(routed(x), plain(x), atol=1e-4)
    got = jax.grad(lambda x: jnp.sum(jnp.sin(routed(x))))(x)
    want = jax.grad(lambda x: jnp.sum(jnp.sin(plain(x))))(x)
    np.testing.assert_allclose(got, want, atol=1e-4)


@pytest.mark.parametrize("tiles,live,want", [
    ([[9, 0], [4, 5]], [[2, 0], [1, 3]], 3.0),   # 18 tiles in 6 runs
    ([[286] * 5] * 16, [[64] * 5] * 16, 286 / 64),
    ([[0, 0]], [[0, 0]], 0.0)],                  # nothing held: no run
    ids=["uneven", "glm_cell", "no_run"])
def test_the_moe_event_says_how_many_tiles_a_weight_copy_hides_under(
        tiles, live, want):
    """``tiles_per_run`` of ``round/<n>/moe`` from hand-made counts (here
    still ``[steps, layers]``; the round sums them over its steps): the
    live row tiles over the (step, layer, expert) triples whose expert got
    a row."""
    from fedml_tpu.train.llm.run_fedllm import FedLLMAPI

    tiles, live = np.asarray(tiles), np.asarray(live)
    steps, layers = tiles.shape
    cfg = ZayaConfig.tiny()
    stats = {"moe_tokens": np.ones((layers, cfg.num_experts), np.int64),
             "moe_live": live.sum(0), "moe_tiles": tiles.sum(0)}
    telemetry.reset_tracer()
    FedLLMAPI._moe_event(3, stats, 64 * steps, steps, cfg)
    (event,) = [r for r in telemetry.get_tracer().records()
                if r["name"] == "round/3/moe"]
    assert event["attrs"]["tiles_per_run"] == pytest.approx(want)


def test_the_yaml_names_the_model():
    class Args:
        model, model_size, lora_rank, num_hidden_layers = "zaya", "tiny", 4, 3

    cfg = config_from_args(Args(), vocab_size=99)
    assert isinstance(cfg, ZayaConfig) and cfg.vocab_size == 99
    assert cfg.num_hidden_layers == 3 and cfg.lora_rank == 4
    assert type(cfg.module()).__name__ == "ZayaForCausalLM"
    Args.model = "llama"
    assert type(config_from_args(Args()).module()).__name__ == \
        "LlamaForCausalLM"
    from fedml_tpu.models import model_hub

    Args.model = "zaya"
    assert type(model_hub.create(Args(), 64)).__name__ == "ZayaForCausalLM"


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
        "model_args": {"model": "zaya", "model_size": "tiny", "lora_rank": 4,
                       "use_flash_attention": False},
        "train_args": {"federated_optimizer": "FedAvg",
                       "client_num_in_total": 4, "client_num_per_round": 2,
                       "comm_round": never, "frequency_of_the_test": never,
                       "local_steps_per_round": 2, "epochs": 2,
                       "per_device_batch_size": 1, "learning_rate": 5e-3,
                       "on_device_round": on_device},
    }))
    return FedLLMAPI(args, None, load_federated(args), mesh=None)


def test_the_fused_round_of_a_tiny_zaya_is_the_host_loops():
    """``fedml_tpu.init`` -> ``FedLLMAPI(on_device_round: true)`` ->
    ``train_one_round``: the same ``compile_federated_round`` as llama's,
    whose fifth output becomes the ``round/<n>/moe`` event; and that
    program against the host loop it replaces (client switch, two steps a
    client, weighted mean), from the same state on the same rows: bfloat16
    compute on both sides, so what is left is XLA's freedom to fuse the two
    programs differently (5e-3 of an adapter's largest entry)."""
    from fedml_tpu.ml.aggregator.agg_operator import FedMLAggOperator

    telemetry.reset_tracer()
    api = _api(on_device=True)
    assert isinstance(api.cfg, ZayaConfig)
    engine = api.client.engine
    copy = lambda t: jax.tree.map(jnp.copy, t)
    p0, o0 = copy(engine.params), copy(engine.opt_state)
    g0 = copy(api.global_exchange)

    report = api.train_one_round(1)
    assert np.isfinite(report["train_loss"])
    records = telemetry.get_tracer().records()
    (moe,) = [r for r in records if r["name"] == "round/1/moe"]
    cfg = api.cfg
    tokens = 2 * 2 * engine.batch_size * engine.seq_len
    assert moe["point"] and moe["attrs"]["dropped"] == 0
    assert moe["attrs"]["layers"] == cfg.num_hidden_layers
    assert moe["attrs"]["experts"] == cfg.num_experts
    assert moe["attrs"]["tokens"] == tokens
    assert moe["attrs"]["steps"] == 2 * 2
    # the one protocol of the event (every family's configuration states
    # ``moe_static`` and ``moe_capacity_rows``): here every expert is held
    assert moe["attrs"]["held"] == cfg.num_experts
    assert moe["attrs"]["top_k"] == 1
    assert moe["attrs"]["assignments"] == tokens
    assert moe["attrs"]["held_share"] == 1.0
    assert moe["attrs"]["capacity_rows"] == cfg.moe_capacity_rows(
        engine.batch_size * engine.seq_len)
    assert 1.0 <= moe["attrs"]["max_over_mean"] <= cfg.num_experts
    assert 1 / cfg.num_experts <= moe["attrs"]["live_share"] <= 1.0
    assert 1.0 <= moe["attrs"]["tiles_per_run"] \
        <= moe["attrs"]["capacity_rows"] / cfg.moe_block_rows
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
    for k, v in host.items():
        scale = float(jnp.abs(v).max())
        np.testing.assert_allclose(api.global_exchange[k], v, rtol=0,
                                   atol=5e-3 * scale, err_msg=k)
        assert float(jnp.abs(v - g0[k]).max()) > 0, k  # and it moved
    telemetry.reset_tracer()
