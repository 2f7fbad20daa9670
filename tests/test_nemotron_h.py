"""``model: nemotron_h`` against its plain float32 reference, on the CPU at
tiny widths with the published row's pattern letters (``MEMEMEM*EME``: five
Mamba, five expert and one attention layer; a quarter of 16 experts held,
top-3; an untied head), on seeded random weights; the chunked scan against
the sequential recurrence; the held shares against the uncut layer; the
layout against a sort; then through ``FedLLMAPI``'s fused round. A timing
here is never a speed."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu import telemetry
from fedml_tpu.models.llm import config_from_args, nemotron_h_reference as ref
from fedml_tpu.models.llm.nemotron_h import (
    RELU2,
    NemotronHConfig,
    NemotronHMoE,
)
from fedml_tpu.ops import grouped_matmul as gmm
from fedml_tpu.ops.ssd import ssd
from fedml_tpu.train.llm.sharding import unbox
from fedml_tpu.train.llm.trainer import (extract_lora, extract_trainable,
                                         merge_lora)

B, T = 2, 20


def _path(path) -> str:
    return "/".join(str(getattr(p, "key", p)) for p in path)


def seeded(cfg, seed=0):
    """``init``'s weights with every leaf that starts at 0 or 1 made random
    (``lora_b``, biases, the selection bias, ``A_log``, ``D``, ``dt_bias``,
    norm scales) and the router's logits spread over more than rounding."""
    tokens = jax.random.randint(jax.random.key(seed + 1), (B, T), 0,
                                cfg.vocab_size)
    params = unbox(jax.jit(cfg.module().init)(jax.random.key(seed), tokens))
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for i, (path, v) in enumerate(flat):
        name, key = _path(path), jax.random.fold_in(jax.random.key(seed + 7), i)
        if "lora_b" in name or name.endswith(("conv_bias", "router_bias")):
            v = 0.05 * jax.random.normal(key, v.shape, v.dtype)
        elif name.endswith(("scale", "/D", "A_log", "dt_bias")):
            v = v + 0.3 * jax.random.normal(key, v.shape, v.dtype)
        elif name.endswith("router_weight"):
            v = 3.0 * v
        out.append(v)
    return jax.tree_util.tree_unflatten(treedef, out), tokens


@pytest.fixture(scope="module")
def f32():
    cfg = NemotronHConfig.tiny(lora_rank=4, dtype=jnp.float32,
                               param_dtype=jnp.float32)
    params, tokens = seeded(cfg)
    return cfg, params, tokens


def test_the_tiny_preset_keeps_the_rows_letters_and_ratios():
    tiny, row = NemotronHConfig.tiny(), NemotronHConfig.nemotron3_super_120b()
    assert row.hybrid_override_pattern[:11] == tiny.hybrid_override_pattern
    assert len(row.hybrid_override_pattern) == row.num_hidden_layers == 88
    assert [row.hybrid_override_pattern.count(k) for k in "ME*"] == [40, 40, 8]
    assert [tiny.layer_kind(i) for i in range(11)] == list("MEMEMEM*EME")
    for cfg in (tiny, row):
        assert cfg.mamba_inner == 2 * cfg.hidden_size
        assert cfg.mamba_num_heads // cfg.n_groups == 16
        assert cfg.num_attention_heads // cfg.num_key_value_heads == 16
        assert cfg.moe_shared_expert_intermediate_size \
            == 2 * cfg.moe_intermediate_size
        assert cfg.conv_dim == cfg.mamba_inner \
            + 2 * cfg.n_groups * cfg.ssm_state_size
    assert (row.experts_total, row.n_routed_experts) == (512, 512)
    assert (tiny.experts_total, tiny.n_routed_experts) == (16, 4)
    for key in ("conv_kernel", "routed_scaling_factor", "norm_topk_prob",
                "tie_word_embeddings", "n_shared_experts"):
        assert getattr(tiny, key) == getattr(row, key), key


@pytest.mark.parametrize("bad", [
    {"hybrid_override_pattern": "MEMX"}, {"tie_word_embeddings": True},
    {"num_hidden_layers": 12}, {"attention_bias": True}, {"n_group": 2},
    {"norm_topk_prob": False}, {"n_shared_experts": 2},
    {"num_experts_per_tok": 17}, {"held_experts_first": 13},
    {"n_groups": 3}])
def test_what_is_not_implemented_is_refused(bad):
    with pytest.raises(ValueError, match="not implemented"):
        NemotronHConfig.tiny(**bad)


def test_logits_loss_and_counts_are_the_references(f32):
    """float32 on both sides: what is left is the order of summation
    (1e-6 of logits of order 1) — the chunked scan against the sequential
    one, the sorted grouped product against the loop with a mask."""
    cfg, params, tokens = f32
    logits, state = cfg.module().apply(params, tokens,
                                       mutable=["intermediates"])
    want, want_counts = ref.forward(cfg, params, tokens)
    np.testing.assert_allclose(logits, want, atol=3e-6, rtol=0)
    sown = {k: v[0] for k, v in state["intermediates"].items()}
    assert set(sown) == set(cfg.round_stats)
    np.testing.assert_array_equal(sown["moe_tokens"], want_counts)
    assert sown["moe_tokens"].shape == (5, cfg.n_routed_experts)
    # every assignment to a held expert got a row: nothing dropped
    np.testing.assert_array_equal(sown["moe_placed"], sown["moe_held"])
    np.testing.assert_array_equal(sown["moe_tokens"].sum(1), sown["moe_held"])
    assert 0 < int(sown["moe_held"].sum()) < 5 * B * T * 3


def test_every_adapter_leafs_gradient_is_the_references(f32):
    """``jax.grad`` of the module's loss against ``jax.grad`` of the plain
    reference's (through the sequential scan), for all 28 adapter leaves;
    float32 both, so 1e-4 of the leaf's largest entry covers the order of
    summation."""
    cfg, params, tokens = f32
    targets = jnp.roll(tokens, -1, axis=1)

    def module_loss(lora):
        logits = cfg.module().apply(merge_lora(params, lora), tokens)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))

    def reference_loss(lora):
        return ref.loss(cfg, merge_lora(params, lora), tokens, targets)

    lora = extract_lora(params)
    assert len(lora) == 2 * (5 * 2 + 4)
    assert {k.split("/")[-2] for k in lora} == {
        "q_proj", "k_proj", "v_proj", "o_proj", "in_proj", "out_proj"}
    loss, got = jax.jit(jax.value_and_grad(module_loss))(lora)
    want_loss, want = jax.jit(jax.value_and_grad(reference_loss))(lora)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    for k in lora:
        scale = float(jnp.abs(want[k]).max())
        assert scale > 0, k
        np.testing.assert_allclose(got[k], want[k], rtol=0, atol=1e-4 * scale,
                                   err_msg=k)


def test_only_the_adapters_train_and_travel(f32):
    """The router's leaves are ``router_weight`` and ``router_bias``, not
    the key ``router`` that ``is_trainable_path`` catches: what the
    optimizer sees and what the round exchanges are the same 28 leaves."""
    _, params, _ = f32
    assert set(extract_trainable(params)) == set(extract_lora(params))
    assert not any("router" in k or "experts" in k or "shared" in k
                   for k in extract_trainable(params))


@pytest.mark.parametrize("t", [0, 7, T - 2])
def test_no_logit_sees_a_later_token(f32, t):
    """Changing every token after ``t`` moves no logit at or before ``t``:
    a convolution, a chunk or a state that looked ahead would. (The
    routing of one token does not depend on another's.)"""
    cfg, params, tokens = f32
    other = tokens.at[:, t + 1:].set((tokens[:, t + 1:] + 3) % cfg.vocab_size)
    a = cfg.module().apply(params, tokens)
    b = cfg.module().apply(params, other)
    np.testing.assert_allclose(a[:, :t + 1], b[:, :t + 1], atol=1e-6, rtol=0)
    assert float(jnp.abs(a[:, t + 1:] - b[:, t + 1:]).max()) > 1e-3


def _scan_inputs(t, heads=4, p=3, groups=2, n=5, seed=0):
    keys = jax.random.split(jax.random.key(seed), 5)
    return (jax.random.normal(keys[0], (2, t, heads, p)),
            jax.nn.softplus(jax.random.normal(keys[1], (2, t, heads)) - 1.0),
            -jnp.exp(jax.random.normal(keys[2], (heads,))),
            jax.random.normal(keys[3], (2, t, groups, n)),
            jax.random.normal(keys[4], (2, t, groups, n)))


@pytest.mark.parametrize("groups", [2, 4], ids=["2_heads_a_group",
                                               "1_head_a_group"])
@pytest.mark.parametrize("t,chunk", [(16, 8), (19, 8), (5, 8), (24, 24),
                                     (33, 4)])
def test_the_chunked_scan_is_the_sequential_recurrence(t, chunk, groups):
    """``ops/ssd.py`` (chunks, one state a chunk, a scan over chunk
    states) against the token-by-token loop of the reference: values, and
    the gradient with respect to all five inputs; also at a ``T`` that is
    not a whole number of chunks (padded inside) and under one chunk, with
    heads that share ``B`` and ``C`` in pairs and with a group a head."""
    args = _scan_inputs(t, groups=groups)
    sequential = jax.vmap(ref.recurrence, in_axes=(0, 0, None, 0, 0))
    got = ssd(*args, chunk=chunk)
    want = sequential(*args)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-5)
    weigh = jax.random.normal(jax.random.key(9), want.shape)
    g = jax.grad(lambda *a: jnp.sum(ssd(*a, chunk=chunk) * weigh),
                 argnums=(0, 1, 2, 3, 4))(*args)
    w = jax.grad(lambda *a: jnp.sum(sequential(*a) * weigh),
                 argnums=(0, 1, 2, 3, 4))(*args)
    for name, a, b in zip("x dt a b c".split(), g, w):
        scale = float(jnp.abs(b).max())
        np.testing.assert_allclose(a, b, atol=1e-4 * scale, rtol=0,
                                   err_msg=name)


def test_the_scan_refuses_heads_that_no_group_divides():
    x, dt, a, b, c = _scan_inputs(8, heads=3)
    with pytest.raises(ValueError, match="not a multiple"):
        ssd(x, dt, a, b, c, chunk=4)


def test_the_scan_leaves_its_plan():
    telemetry.reset_tracer()
    ssd(*_scan_inputs(19), chunk=8)
    (plan,) = [r for r in telemetry.get_tracer().records()
               if r["name"] == "ssd/plan"]
    assert plan["point"] and plan["attrs"] == {
        "rows": 38, "heads": 4, "head_dim": 3, "groups": 2, "state": 5,
        "chunk": 8, "chunks": 3, "form": "chunked_xla",
        "dtype": "float32"}
    telemetry.reset_tracer()


def test_the_held_shares_add_up_to_the_uncut_layer(f32):
    """The share test: one expert layer's router and latent projections,
    16 experts; the four chips of the deployment hold 4 each. What each
    share's module gives for its held range, less the shared expert (which
    every chip computes alike, so it is counted once), added over the four
    ranges equals the reference's uncut layer (all 16 held)."""
    cfg, _, _ = f32
    whole = NemotronHConfig.tiny(
        dtype=jnp.float32, param_dtype=jnp.float32, n_routed_experts=16,
        n_routed_experts_total=16)
    u = jax.random.normal(jax.random.key(3), (B, T, cfg.hidden_size))
    p = unbox(NemotronHMoE(whole).init(jax.random.key(4), u))["params"]
    p["router_weight"] = 3.0 * p["router_weight"]
    p["router_bias"] = 0.05 * jax.random.normal(jax.random.key(5), (16,))
    flat_u = u.reshape(B * T, -1)
    with jax.default_matmul_precision("highest"):
        want, counts = ref.moe(whole, flat_u, p)
        shared = ref.shared(whole, flat_u, p)
    assert int(counts.sum()) == B * T * 3
    total, held = jnp.zeros_like(want), 0
    for first in (0, 4, 8, 12):
        share = NemotronHConfig.tiny(
            dtype=jnp.float32, param_dtype=jnp.float32,
            held_experts_first=first)
        mine = dict(p, experts={k: v[first:first + 4]
                                for k, v in p["experts"].items()})
        out, stats = NemotronHMoE(share).apply({"params": mine}, u)
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


def _old_group_layout(expert, groups, block_m):
    """``group_layout`` as it stood before this family (top-1 over all
    experts, ranks by an ``[m, m]`` grid): the oracle of the special case."""
    m = expert.shape[0]
    rows = gmm.padded_rows(m, groups, block_m)
    token = jnp.arange(m, dtype=jnp.int32)
    mine = expert[:, None] == jnp.arange(groups, dtype=jnp.int32)
    counts = jnp.sum(mine, axis=0, dtype=jnp.int32)
    run_tiles = -(-counts // block_m)
    tile_end = jnp.cumsum(run_tiles)
    run_start = (tile_end - run_tiles) * block_m
    rank = jnp.sum((expert[None, :] == expert[:, None])
                   & (token[None, :] < token[:, None]), axis=1,
                   dtype=jnp.int32)
    pos = jnp.sum(jnp.where(mine, run_start, 0), axis=1) + rank
    hit = pos[None, :] == jnp.arange(rows, dtype=jnp.int32)[:, None]
    src = jnp.sum(jnp.where(hit, token, 0), axis=1)
    live = tile_end[-1]
    tile = jnp.minimum(jnp.arange(rows // block_m, dtype=jnp.int32), live - 1)
    tile_group = jnp.sum(tile_end[None, :] <= tile[:, None], axis=1,
                         dtype=jnp.int32)
    return src, jnp.any(hit, axis=1), pos, tile_group, live[None], counts


@pytest.mark.parametrize("sizes", [
    [6, 3, 4, 23, 4], [0, 40, 0, 0, 0], [8, 8, 8, 8, 8], [1, 0, 0, 0, 39]],
    ids=["uneven", "one_expert", "whole_tiles", "ends"])
def test_top_1_over_all_experts_is_todays_layout(sizes):
    rng = np.random.default_rng(0)
    expert = jnp.asarray(rng.permutation(np.repeat(np.arange(len(sizes)),
                                                   sizes)), jnp.int32)
    new = gmm.group_layout(expert, len(sizes), 8)
    src, valid, pos, tile_group, live, counts = _old_group_layout(
        expert, len(sizes), 8)
    np.testing.assert_array_equal(new.valid, valid)
    np.testing.assert_array_equal(jnp.where(new.valid, new.src, 0),
                                  jnp.where(valid, src, 0))
    np.testing.assert_array_equal(new.pos, pos)
    assert bool(new.held.all()) and new.pos.shape == (40,)
    np.testing.assert_array_equal(new.tile_group, tile_group)
    np.testing.assert_array_equal(new.live_tiles, live)
    np.testing.assert_array_equal(new.counts, counts)


def _choices(m, k, total, rng, force=None):
    """``k`` distinct experts of ``total`` a token; ``force`` = a range all
    of every token's choices lie in."""
    lo, hi = force or (0, total)
    return jnp.asarray(np.stack([lo + rng.permutation(hi - lo)[:k]
                                 for _ in range(m)]), jnp.int32)


@pytest.mark.parametrize("case", ["some_held", "all_held", "none_held",
                                  "one_choice_a_token"])
def test_the_layout_with_top_k_and_a_held_range_is_a_sorts(case):
    """Against an oracle that sorts: the held assignments in order of
    (expert, token), every run padded to whole tiles; the others skipped.
    ``all_held``: every one of every token's choices is held here, which
    is what the buffer's rows are planned for."""
    rng = np.random.default_rng(1)
    m, k, total, first, held, bm = 13, 3, 12, 4, 5, 4
    force = {"all_held": (4, 9), "none_held": (9, 12)}.get(case)
    k = 1 if case == "one_choice_a_token" else k
    chosen = _choices(m, k, total, rng, force)
    layout = gmm.group_layout(chosen, held, bm, first)
    rows = gmm.padded_rows(m * k, held, bm)
    assert layout.src.shape == layout.valid.shape == (rows,)
    assert layout.pos.shape == layout.held.shape == (m, k)
    c = np.asarray(chosen)
    want_src, want_pos, at = {}, {}, 0
    counts = []
    for e in range(first, first + held):
        run = [(t, j) for t in range(m) for j in range(k) if c[t, j] == e]
        for r, (t, j) in enumerate(run):
            want_src[at + r] = t * k + j
            want_pos[(t, j)] = at + r
        counts.append(len(run))
        at += -(-len(run) // bm) * bm
    np.testing.assert_array_equal(layout.counts, counts)
    np.testing.assert_array_equal(layout.live_tiles, [at // bm])
    valid = np.asarray(layout.valid)
    assert sorted(np.flatnonzero(valid)) == sorted(want_src)
    for row, a in want_src.items():
        assert int(layout.src[row]) == a
    in_range = (c >= first) & (c < first + held)
    np.testing.assert_array_equal(layout.held, in_range)
    for (t, j), row in want_pos.items():
        assert int(layout.pos[t, j]) == row
    if case == "all_held":
        assert in_range.all() and valid.sum() == m * k
    if case == "none_held":
        assert not valid.any() and int(layout.live_tiles[0]) == 0
    # the tile table names each live tile's expert
    for tile in range(at // bm):
        row = tile * bm
        e = c.reshape(-1)[want_src[row]] - first
        assert int(layout.tile_group[tile]) == e


@pytest.mark.parametrize("interpret", [None, True],
                         ids=["reference", "interpreter"])
@pytest.mark.parametrize("m,force", [
    (11, None), (11, (2, 6)), (11, (6, 9)), (60, (2, 6)), (40, (5, 8))],
    ids=["some_held", "all_held", "none_held", "long_runs", "one_run"])
@pytest.mark.parametrize("activation", [None, RELU2],
                         ids=["plain", "relu2"])
def test_grouped_product_over_a_tokens_choices_and_its_gradient(
        activation, m, force, interpret):
    """dispatch -> ``moe_gmm`` -> combine with three choices a token and
    experts 2..5 of 9 held, against a gather of each held assignment's own
    matrix: values (zero for an assignment that is not held), and the
    gradient with respect to the tokens' rows. With an activation the
    product applies it to its rows and its row gradient carries the
    derivative: both equal ``f`` written outside and differentiated by
    JAX. Three column tiles, so the kernels' weight copies cross from one
    to the next: ``none_held`` has no live tile at all (no copy is asked
    for), ``long_runs`` four runs of about 6 tiles, ``one_run`` one live
    run of 5 tiles before 14 dead ones (every token chooses 5, 6 and 7,
    of which 5 is held)."""
    rng = np.random.default_rng(2)
    k, total, first, held, kk, n, bm = 3, 9, 2, 4, 32, 48, 8
    chosen = _choices(m, k, total, rng, force)
    x = jnp.asarray(rng.normal(size=(m, kk)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(held, kk, n)), jnp.float32)
    gate = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    here = (chosen >= first) & (chosen < first + held)
    f = activation.value if activation else (lambda x: x)

    def routed(x):
        layout = gmm.group_layout(chosen, held, bm, first)
        out = gmm.grouped_matmul(gmm.dispatch(x, layout), w, layout, bm, 16,
                                 interpret=interpret, activation=activation)
        return jnp.sum(gmm.combine(out, layout) * gate[..., None], axis=1)

    def plain(x):
        own = w[jnp.clip(chosen - first, 0, held - 1)]          # [m, k, K, N]
        out = jnp.einsum("mk,mjkn->mjn", f(x), own)
        return jnp.sum(jnp.where(here[..., None], out, 0) * gate[..., None],
                       axis=1)

    # float32 sums in two orders; a wrong expert's block is off by O(1)
    np.testing.assert_allclose(routed(x), plain(x), atol=1e-4, rtol=1e-4)
    got = jax.grad(lambda x: jnp.sum(jnp.sin(routed(x))))(x)
    want = jax.grad(lambda x: jnp.sum(jnp.sin(plain(x))))(x)
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)
    assert np.isfinite(np.asarray(got)).all()


@pytest.mark.parametrize("interpret", [None, True],
                         ids=["reference", "interpreter"])
@pytest.mark.parametrize("top_1", [True, False], ids=["top_1", "top_k"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_product_applies_what_stands_before_it_on_every_held_row(
        dtype, top_1, interpret):
    """``grouped_matmul(x, w, layout, activation=f)`` (``f`` = relu^2) against
    ``grouped_matmul(f(x), w, layout)`` differentiated by JAX, row by row
    of the padded buffer: value and row gradient agree on every row that
    holds an assignment (top-1 with all held: ``zaya``'s layout; top-k
    with a held range: ``nemotron_h``'s). In bfloat16 the value is the
    same to the BIT (``f`` through float32, rounded once, is what ``f``
    outside feeds the product) and the gradient within one rounding (the
    kernel scales its float32 product and rounds once; outside, the
    product is rounded first)."""
    rng = np.random.default_rng(5)
    m, kk, n, bm = 19, 32, 48, 8
    if top_1:
        held, first = 5, 0
        chosen = jnp.asarray(rng.integers(0, held, size=m), jnp.int32)
    else:
        held, first = 4, 2
        chosen = _choices(m, 3, 9, rng)
    layout = gmm.group_layout(chosen, held, bm, first)
    rows = layout.valid.shape[0]
    x = jnp.asarray(rng.normal(size=(rows, kk)), dtype)
    w = jnp.asarray(rng.normal(size=(held, kk, n)), dtype)
    dy = jnp.asarray(rng.normal(size=(rows, n)), dtype)
    product = lambda x, **kw: gmm.grouped_matmul(
        x, w, layout, bm, 16, interpret=interpret, **kw)

    got, pull = jax.vjp(lambda x: product(x, activation=RELU2), x)
    want, pull_plain = jax.vjp(lambda x: product(RELU2.value(x)), x)
    valid = np.asarray(layout.valid)
    assert valid.any() and not valid.all()
    held_rows = lambda y: np.asarray(y.astype(jnp.float32))[valid]
    exact = dict(atol=0, rtol=0)
    near = dict(atol=1e-4, rtol=1e-5)
    np.testing.assert_allclose(
        held_rows(got), held_rows(want),
        **(exact if dtype == jnp.bfloat16 else near))
    np.testing.assert_allclose(
        held_rows(pull(dy)[0]), held_rows(pull_plain(dy)[0]),
        **(dict(atol=2.0 ** -6, rtol=2.0 ** -6) if dtype == jnp.bfloat16
           else near))


@jax.custom_vjp
def _poison_dead_rows(y, dead):
    """NaN in every row of a dead tile, on the way there and on the way
    back: whatever a kernel leaves unwritten there."""
    return jnp.where(dead[:, None], jnp.nan, y)


_poison_dead_rows.defvjp(
    lambda y, dead: (_poison_dead_rows(y, dead), dead),
    lambda dead, g: (jnp.where(dead[:, None], jnp.nan, g), None))


@pytest.mark.parametrize("interpret", [None, True],
                         ids=["reference", "interpreter"])
@pytest.mark.parametrize("force", [None, (2, 6), (6, 9)],
                         ids=["some_held", "all_held", "none_held"])
@pytest.mark.parametrize("activation", [None, RELU2],
                         ids=["plain", "relu2"])
def test_nothing_reads_the_rows_of_dead_tiles(activation, force, interpret):
    """dispatch -> product -> product (with the activation) -> combine
    with NaN planted in the dead tiles' rows of every buffer, forward and
    backward: the loss and the tokens' gradient stay finite and equal the
    unpoisoned ones, because ``combine`` gathers each held assignment's
    own row and ``dispatch``'s transpose selects by ``held``."""
    rng = np.random.default_rng(7)
    m, k, total, first, held, lat, mid, bm = 11, 3, 9, 2, 4, 16, 32, 8
    chosen = _choices(m, k, total, rng, force)
    x = jnp.asarray(rng.normal(size=(m, lat)), jnp.float32)
    up = jnp.asarray(rng.normal(size=(held, lat, mid)), jnp.float32)
    down = jnp.asarray(rng.normal(size=(held, mid, lat)), jnp.float32)
    gate = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)

    def loss(x, poison):
        layout = gmm.group_layout(chosen, held, bm, first)
        rows = layout.valid.shape[0]
        dead = jnp.arange(rows) >= layout.live_tiles[0] * bm
        assert rows > m * k   # the plan's rows exceed any routing's
        spoil = (lambda y: _poison_dead_rows(y, dead)) if poison \
            else (lambda y: y)
        product = lambda a, w, **kw: gmm.grouped_matmul(
            a, w, layout, bm, 16, interpret=interpret, **kw)
        hidden = spoil(product(spoil(gmm.dispatch(x, layout)), up))
        out = spoil(product(hidden, down, activation=activation))
        return jnp.sum(gmm.combine(out, layout) * gate[..., None])

    value, grad = jax.value_and_grad(loss)(x, True)
    clean, clean_grad = jax.value_and_grad(loss)(x, False)
    assert np.isfinite(float(value)) and np.isfinite(np.asarray(grad)).all()
    np.testing.assert_allclose(value, clean, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(grad, clean_grad, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n,tile", [(2688, 896), (2048, 1024), (1024, 1024),
                                    (48, 48), (5376, 896), (384, 384)])
def test_the_column_tile_is_chosen_from_the_columns(n, tile):
    assert gmm.column_tile(n) == tile and n % tile == 0


def test_columns_that_no_tile_divides_are_refused():
    with pytest.raises(ValueError, match="no tile"):
        gmm.column_tile(1100)
    assert gmm.column_tile(48, 16) == 16


def test_the_yaml_names_the_model():
    class Args:
        model, model_size, lora_rank = "nemotron_h", "tiny", 4
        n_routed_experts, held_experts_first = 8, 8

    cfg = config_from_args(Args(), vocab_size=99)
    assert isinstance(cfg, NemotronHConfig) and cfg.vocab_size == 99
    assert (cfg.n_routed_experts, cfg.held_experts_first,
            cfg.experts_total, cfg.lora_rank) == (8, 8, 16, 4)
    assert type(cfg.module()).__name__ == "NemotronHForCausalLM"
    assert [cfg.module().layer_block(i).kind for i in range(11)] \
        == list("MEMEMEM*EME")
    from fedml_tpu.models import model_hub

    assert type(model_hub.create(Args(), 64)).__name__ == \
        "NemotronHForCausalLM"


def test_a_cache_is_refused(f32):
    cfg, params, tokens = f32
    with pytest.raises(NotImplementedError, match="serving"):
        cfg.module().apply(params, tokens, kv_caches=[()] * 11)


def _api(on_device: bool, model: str = "nemotron_h"):
    import fedml_tpu
    from fedml_tpu.arguments import load_arguments_from_dict
    from fedml_tpu.data import load_federated
    from fedml_tpu.train.llm.run_fedllm import FedLLMAPI

    never = 1 << 30
    args = fedml_tpu.init(load_arguments_from_dict({
        "common_args": {"training_type": "simulation", "random_seed": 0},
        "data_args": {"dataset": "synthetic_lm", "max_seq_length": 16,
                      "vocab_size": 64, "train_size": 64, "test_size": 16},
        "model_args": {"model": model, "model_size": "tiny",
                       "lora_rank": 4, "use_flash_attention": False},
        "train_args": {"federated_optimizer": "FedAvg",
                       "client_num_in_total": 4, "client_num_per_round": 2,
                       "comm_round": never, "frequency_of_the_test": never,
                       "local_steps_per_round": 2, "epochs": 2,
                       "per_device_batch_size": 1, "learning_rate": 5e-3,
                       "on_device_round": on_device},
    }))
    return FedLLMAPI(args, None, load_federated(args), mesh=None)


@pytest.mark.parametrize("family", ["nemotron_h", "zaya"])
def test_every_grouped_product_of_a_round_leaves_its_plan(family,
                                                          monkeypatch):
    """Tracing the tiny fused round with the grouped products as KERNELS
    (the interpreter's form, steered here: off a TPU a family's own call
    gets the reference, whose row gradient is XLA's and leaves nothing)
    leaves one ``moe_gmm/plan`` a product and direction. ``nemotron_h``
    hands relu^2 to its second product, so two of an expert layer's four
    say so — that product and its row gradient; ``zaya``'s SwiGLU takes
    two products' outputs and stays outside: none of its six a layer."""
    monkeypatch.setattr(gmm, "kernel_mode", lambda *a, **kw: gmm.INTERPRET)
    api = _api(on_device=True, model=family)
    engine, cfg = api.client.engine, api.cfg
    feed = jax.ShapeDtypeStruct((2, 2, engine.batch_size, engine.seq_len),
                                np.int32)
    telemetry.reset_tracer()
    engine.compile_federated_round(2, 2).lower(
        engine.params, engine.opt_state, api.global_exchange, feed, feed,
        jax.ShapeDtypeStruct(feed.shape[:3], np.float32),
        jax.ShapeDtypeStruct(feed.shape[:1], np.float32))
    records = telemetry.get_tracer().records()
    assert all(r["point"] for r in records if r["name"] == "moe_gmm/plan")
    plans = [r["attrs"] for r in records if r["name"] == "moe_gmm/plan"]
    rows = cfg.moe_capacity_rows(engine.batch_size * engine.seq_len)
    assert plans and all(
        p["rows"] == rows and p["block_m"] == cfg.moe_block_rows
        and p["row_tiles"] * p["block_m"] == rows
        and p["column_tiles"] * p["block_n"] == p["n"]
        and p["form"] == "interpret" and p["dtype"] == "bfloat16"
        and p["weight_prefetch"] == "run" and p["weight_slots"] == 2
        for p in plans)
    fused = [p for p in plans if p["activation"] is not None]
    if family == "zaya":
        assert len(plans) == 6 * cfg.num_hidden_layers and not fused
        return
    layers = cfg.hybrid_override_pattern.count("E")
    lat, mid = cfg.moe_latent_size, cfg.moe_intermediate_size
    assert len(plans) == 4 * layers and len(fused) == 2 * layers
    assert {p["activation"] for p in fused} == {"relu2"}
    # the second product [mid -> latent] and its row gradient
    assert sorted((p["transpose"], p["k"], p["n"]) for p in fused) == (
        [(False, mid, lat)] * layers + [(True, lat, mid)] * layers)
    assert sorted((p["transpose"], p["k"], p["n"]) for p in plans
                  if p not in fused) == (
        [(False, lat, mid)] * layers + [(True, mid, lat)] * layers)


def test_the_fused_round_of_a_tiny_nemotron_is_the_host_loops():
    """``fedml_tpu.init`` -> ``FedLLMAPI(on_device_round: true)`` ->
    ``train_one_round``: the same ``compile_federated_round`` as the other
    families', whose fifth output becomes the ``round/<n>/moe`` event with
    the held range's counts; the scan leaves ``ssd/plan``; and that program
    against the host loop it replaces, from the same state on the same
    rows (bfloat16 compute on both sides: 5e-3 of an adapter's largest
    entry covers XLA's freedom to fuse the two programs differently)."""
    from fedml_tpu.ml.aggregator.agg_operator import FedMLAggOperator
    from fedml_tpu.telemetry.profiling import get_catalog

    telemetry.reset_tracer()
    api = _api(on_device=True)
    assert isinstance(api.cfg, NemotronHConfig)
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
            attrs["top_k"]) == (5, 16, 4, 3)
    assert attrs["tokens"] == tokens and attrs["steps"] == 2 * 2
    assert attrs["assignments"] == tokens * 3
    assert 0.0 < attrs["held_share"] < 1.0
    assert attrs["capacity_rows"] == cfg.moe_capacity_rows(
        engine.batch_size * engine.seq_len)
    assert 1.0 <= attrs["max_over_mean"] <= cfg.n_routed_experts
    assert 0.0 < attrs["live_share"] <= 1.0
    assert 1.0 <= attrs["tiles_per_run"] \
        <= attrs["capacity_rows"] / cfg.moe_block_rows
    plans = [r["attrs"] for r in records if r["name"] == "ssd/plan"]
    assert plans and all(
        (p["heads"], p["head_dim"], p["groups"], p["state"], p["chunk"])
        == (16, 4, 1, 8, 8) and p["form"] == "chunked_xla" for p in plans)
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
    assert len(host) == 28
    for k, v in host.items():
        scale = float(jnp.abs(v).max())
        np.testing.assert_allclose(api.global_exchange[k], v, rtol=0,
                                   atol=5e-3 * scale, err_msg=k)
        assert float(jnp.abs(v - g0[k]).max()) > 0, k  # and it moved
    telemetry.reset_tracer()
