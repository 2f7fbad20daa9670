"""``families/glm4_moe_lite``: its counts, its scopes, its reference against
the repo's own, and the whole run after the look for a chip, at tiny widths
on the CPU with the real row's shape (one leading dense layer, then expert
layers; rotary lanes a quarter of a head; top-2 of 8 gated experts beside a
shared one; an untied head)."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import run as bench_run
from benchmarks.harness import check, reducers, scopes, spec, weights
from benchmarks.harness.reference import Reference
from benchmarks.harness.trace_reduce import Trace

from .test_correct_fails import (_broken_build, half_left_out,
                                 state_unchanged)
from .test_rehearsal import PEAKS, FakeDevice

REAL = "glm-4.7-flash.round-4k"
TINY = {
    "source": "test only", "model_type": "glm4_moe_lite",
    "attention_bias": False, "hidden_act": "silu", "hidden_size": 64,
    "intermediate_size": 320, "max_position_embeddings": 128,
    "moe_intermediate_size": 48, "topk_method": "noaux_tc",
    "norm_topk_prob": True, "num_attention_heads": 4, "n_group": 1,
    "topk_group": 1, "n_routed_experts": 8, "n_shared_experts": 1,
    "routed_scaling_factor": 1.8, "num_experts_per_tok": 2,
    "first_k_dense_replace": 1, "num_hidden_layers": 3,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 1000000, "tie_word_embeddings": False, "q_lora_rank": 24,
    "kv_lora_rank": 16, "qk_nope_head_dim": 12, "qk_rope_head_dim": 4,
    "v_head_dim": 16, "vocab_size": 256,
    "published": {"num_hidden_layers": 47},
    "run": {"lora_rank": 4, "lora_alpha": 4.0,
            "lora_targets": ["q_a_proj", "q_b_proj", "kv_a_proj",
                             "kv_b_proj", "o_proj"],
            "base_dtype": "bfloat16", "compute_dtype": "bfloat16",
            "adapter_dtype": "float32", "use_flash_attention": True,
            "moe_block_rows": 8}}
# from CPU readings of this tiny cell on 6 seeds, two past 2**31
# (calibrate.py --any-device; the real cell's limits are read on the chip at
# its own size): the program reads grad 0.0045-0.0100 on five of them and
# 0.0226 on one (32 tokens a step and 2 of 8 experts a token: one token the
# bfloat16 stream routes otherwise than the float32 one is 3 % of a step,
# the routing hazard of PERF.md), grad2 0.0020-0.0070, change 0.0051-0.0117;
# the reference in bfloat16 at most 0.0052 / 0.0026 / 0.0065; the fp8
# control grad from 0.0340, grad2 from 0.0308, change from 0.0284 (it fails
# each on every seed); half of the clients left out 0.24 / 0.31 / 0.31
TINY_LIMITS = {"limits": {"count": 0, "grad": 0.03, "grad2": 0.015,
                          "change": 0.02}}
CELL = "tiny-glm.round-tiny"


@pytest.fixture(autouse=True)
def _no_events_left_behind():
    """The process tracer is one ring for the whole run: a later file's
    tests read every ``round/<n>/moe`` event in it as their own family's."""
    from fedml_tpu.telemetry import reset_tracer

    yield
    reset_tracer()


@pytest.fixture()
def glm_root(tiny_root):
    """``conftest.tiny_root`` with a tiny glm4_moe_lite configuration and
    its cell added, again by new files and new entries alone."""
    bench = os.path.join(tiny_root, "benchmarks")
    with open(os.path.join(bench, "configs", "tiny-glm.json"), "w") as f:
        json.dump(TINY, f)
    with open(os.path.join(bench, "limits", CELL + ".json"), "w") as f:
        json.dump(TINY_LIMITS, f)
    path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(path) as f:
        bm = json.load(f)
    bm["configs"].append({"name": "tiny-glm", "source": "test only",
                          "file": "benchmarks/configs/tiny-glm.json",
                          "reduced": sorted(TINY["published"]), "why": "test"})
    bm["workloads"].append({"name": CELL, "config": "tiny-glm",
                            "traffic": "round-tiny", "chips": 1,
                            "why": "test"})
    for m in bm["per_layer"]:
        if m.get("workloads") == [REAL]:
            m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(bm, f)
    return tiny_root


def _real():
    return spec.Cell(REAL)


def test_the_configuration_is_the_row_cut_in_depth_alone():
    cell = _real()
    assert cell.config_entry["reduced"] == ["num_hidden_layers"]
    assert cell.entry["chips"] == 1 and cell.entry["traffic"] == "round-4k"
    config = cell.config
    # every published width
    assert [config[k] for k in (
        "hidden_size", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "num_attention_heads",
        "intermediate_size", "moe_intermediate_size", "n_routed_experts",
        "num_experts_per_tok", "vocab_size")] == [
        2048, 768, 512, 192, 64, 256, 20, 10240, 1536, 64, 4, 154880]
    assert config["published"] == {"num_hidden_layers": 47}
    assert (config["num_hidden_layers"], config["first_k_dense_replace"]) \
        == (6, 1)
    assert "8 pipeline stages" in config["deployment"]
    assert cell.traffic["seq_len"] == 4096
    assert cell.traffic["clients_per_round"] * cell.traffic["local_steps"] == 16
    assert set(cell.limits["limits"]) == {
        "loss", "count", "grad", "grad2", "change"}
    # three times of room from the largest sound reading and from the
    # planted fault's smallest (set_from gives both)
    assert 3 * 1.36e-5 < cell.limits["limits"]["loss"] < 1.5e-4 / 3
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog beside the guide here")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "GLM-4.7-Flash")
    assert cell.config_entry["source"] == row["source_url"] \
        == config["source"]
    changed = {k for k, v in row["config"].items() if config[k] != v}
    assert changed == {"num_hidden_layers"}
    assert config["published"]["num_hidden_layers"] \
        == row["config"]["num_hidden_layers"]


def test_model_flops_count_the_experts_a_token_chose():
    cell = _real()
    config, flops = cell.config, cell.family.flops
    base = weights.param_count(cell.family.weights, config)
    assert base == config["parameters"] == 3895625536
    tokens = 65536
    got = flops.model_flops(config, tokens, 4096)
    assert got["total"] == got["base"] + got["lora"] + got["attention"]
    p = flops.active_matmul_params(config)
    assert p["one_expert"] == 3 * 2048 * 1536 == 9437184
    assert p["attn"] == 21759232 - 768 - 512      # less both latent norms
    assert p["dense"] == 62914560
    # the leaves a token is multiplied by: all but the norm scales, the
    # selection bias, the embedding rows it only looks up, and all but 4
    # of the 64 experts of a layer
    small = 6 * (2 * 2048 + 768 + 512) + 2048 + 5 * 64 + 154880 * 2048
    active = base - small - 5 * (64 - 4) * p["one_expert"]
    assert 6 * p["attn"] + p["dense"] + 5 * p["expert"] + p["head"] == active
    assert got["base"] == 4 * active * tokens
    assert active == pytest.approx(0.747e9, rel=0.01)
    assert weights.param_count(cell.family.weights, config, trainable=True) \
        == 6 * flops.lora_params_per_layer(config) == 2684928
    # causal attention at half of the square: 6 products of 256 lanes
    assert got["attention"] == 6 * 4096 * 4096 * 256 * 20 * 6 * 16
    flash = flops.flash_work(config, tokens, 4096)
    assert flash["flops"] == 9 * 4096 * 4096 * 256 * 20 * 6 * 16
    assert flash["flops"] / 197e12 > 4 * flash["bytes"] / 819e9  # compute
    work = flops.moe_gated_gmm_work(config, tokens, 4096)
    assert work["flops"] == 6 * 5 * 2 * 2048 * 1536 * tokens * 4
    # an expert sees 256 rows a step: at the ridge, bytes and operations
    # within a quarter of each other
    by_bytes, by_flops = work["bytes"] / 819e9, work["flops"] / 197e12
    assert 0.75 < by_bytes / by_flops < 1.25
    less = flops.moe_gated_gmm_work(config, tokens, 4096, 0.5)
    assert less["bytes"] < work["bytes"] and less["flops"] == work["flops"]


R = "jit(fed_round)/while/body/closed_call/while/body/closed_call"
FWD = R + "/jvp(GlmMoeLiteForCausalLM)"
BWD = R + "/transpose(jvp(GlmMoeLiteForCausalLM))"
CALL = 'custom-call(%a), custom_call_target="tpu_custom_call"'
# (instruction, the rest of its line, op_name, seconds, the part it lands in)
OPS = [
    ("moe_gmm.3", CALL, FWD + "/layer_1/moe/experts/moe_gmm/pallas_call",
     2.0, "mlp"),
    ("moe_gmm_t.4", CALL, BWD + "/layer_1/moe/experts/moe_gmm_t/pallas_call",
     1.0, "mlp"),
    ("fusion.5", "fusion(%a), kind=kLoop",
     FWD + "/layer_1/moe/experts/mul", 0.5, "mlp"),
    ("fusion.6", "fusion(%a), kind=kOutput",
     FWD + "/layer_1/moe/router/dot_general", 0.25, "moe_gated_router"),
    ("fusion.7", "fusion(%a), kind=kLoop",
     FWD + "/layer_1/moe/router/top_k", 0.25, "moe_gated_router"),
    ("fusion.8", "fusion(%a), kind=kLoop",
     FWD + "/layer_1/moe/moe_dispatch/gather", 0.25, "moe_gated_dispatch"),
    ("fusion.9", "fusion(%a), kind=kLoop",
     BWD + "/layer_1/moe/moe_combine/mul", 0.125, "moe_gated_dispatch"),
    ("fusion.10", "fusion(%a), kind=kOutput",
     FWD + "/layer_1/moe/shared/up_proj/dot_general", 0.5,
     "moe_gated_shared"),
    ("fusion.11", "fusion(%a), kind=kLoop",
     BWD + "/layer_1/moe/shared/mul", 0.125, "moe_gated_shared"),
    ("fusion.12", "fusion(%a), kind=kOutput",
     FWD + "/layer_0/mlp/gate_proj/dot_general", 1.0, "mlp"),
    ("fusion.13", "fusion(%a), kind=kOutput",
     FWD + "/layer_0/attn/q_a_proj/dot_general", 0.25, "attn_proj"),
    ("fusion.14", "fusion(%a), kind=kOutput",
     BWD + "/layer_2/attn/kv_b_proj/dot_general", 0.5, "attn_proj"),
    ("fusion.15", "fusion(%a), kind=kOutput",
     FWD + "/layer_2/attn/o_proj/dot_general", 0.25, "attn_proj"),
    ("fusion.16", "fusion(%a), kind=kLoop",
     FWD + "/layer_0/attn/q_a_norm/rsqrt", 0.125, "mla_latent"),
    ("fusion.17", "fusion(%a), kind=kLoop",
     BWD + "/layer_0/attn/kv_a_norm/mul", 0.125, "mla_latent"),
    ("fusion.18", "fusion(%a), kind=kLoop",
     FWD + "/layer_0/attn/mla_assemble/concatenate", 0.25, "mla_latent"),
    ("fusion.19", "fusion(%a), kind=kOutput",
     FWD + "/layer_0/attn/mla_assemble/closed_call/rope/bhtd,de->bhte/"
     "dot_general", 0.125, "mla_latent"),
    ("fusion.20", "fusion(%a), kind=kOutput",
     FWD + "/layer_0/attn/closed_call/rope/bhtd,de->bhte/dot_general", 0.25,
     "attn_glue"),
    ("flash_fwd.21", CALL, FWD + "/layer_0/attn/flash_fwd/pallas_call", 1.0,
     "flash_fwd"),
    ("flash_bwd_dkv.22", CALL,
     BWD + "/layer_0/attn/flash_bwd_dkv/pallas_call", 1.5, "flash_dkv"),
    ("fusion.23", "fusion(%a), kind=kLoop",
     FWD + "/layer_0/attn/attn_layout/transpose", 0.25, "attn_glue"),
    ("fusion.24", "fusion(%a), kind=kLoop",
     FWD + "/layer_2/post_attn_norm/mul", 0.25, "norm"),
    ("fusion.25", "fusion(%a), kind=kOutput", FWD + "/lm_head/dot_general",
     1.0, "head_loss"),
    ("fusion.26", "fusion(%a), kind=kLoop", R + "/optimizer/add", 0.125,
     "round_glue"),
    ("slice-done.27", "async-done(%s)", None, 0.0625, "unattributed"),
]
HLO = "HloModule jit_fed_round\n\nENTRY %main (a: f32[8]) -> f32[8] {\n" + \
    "".join(f"  %{name} = f32[8]{{0}} {rest}"
            + (f', metadata={{op_name="{op}"}}' if op else "") + "\n"
            for name, rest, op, _, _ in OPS) + "}\n"
NEW_PARTS = ["mla_latent", "moe_gated_router", "moe_gated_dispatch",
             "moe_gated_shared"]
STANDING = {"flash_fwd", "flash_dq", "flash_dkv", "attn_proj", "attn_glue",
            "mlp", "norm", "head_loss", "round_glue", "unattributed"}


def _trace():
    at, events = 0.0, []
    for name, rest, _, seconds, _ in OPS:
        events.append((f"%{name} = f32[8]{{0}} {rest}", at, at + seconds, 0))
        at += seconds
    return Trace(events, [], [("bench.round", 0.0, at)]), at


def test_the_familys_parts_and_the_ten_that_stand_sum_to_busy_time():
    family = spec.Family("glm4_moe_lite")
    assert [p for p, _ in family.scopes] == ["mlp", "attn_proj"] + NEW_PARTS
    trace, busy = _trace()
    got = scopes.seconds_by_part(trace, scopes.instruction_op_names(HLO),
                                 scopes.load_rules(family.scopes))
    want = {}
    for _, _, _, seconds, part in OPS:
        want[part] = want.get(part, 0.0) + seconds
    assert got == pytest.approx(want)
    assert set(got) <= STANDING | set(NEW_PARTS)
    assert sum(got.values()) == pytest.approx(busy)
    # without the family's pairs a latent projection would be attention
    # glue: the shared pair knows q, k, v and o alone
    shared = scopes.load_rules()
    label = "fusion.13 " + FWD + "/layer_0/attn/q_a_proj/dot_general"
    assert scopes.part_of(label, shared) == "attn_glue"
    assert scopes.part_of(label, scopes.load_rules(family.scopes)) \
        == "attn_proj"


def test_every_new_metric_reads_its_part_its_kernel_or_its_event():
    cell = _real()
    trace, busy = _trace()
    ctx = {"trace": trace, "config": cell.config, "traffic": cell.traffic,
           "family": cell.family, "hlo_text": HLO, "span_records": [],
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
           "rounds": 1, "tokens": 65536}
    by_name = {m["name"]: m for m in cell.per_layer()}
    new = {m for m, v in by_name.items() if v.get("workloads") == [REAL]}
    assert new == {"mla_latent_time_pct", "moe_gated_router_time_pct",
                   "moe_gated_dispatch_time_pct", "moe_gated_shared_time_pct",
                   "moe_gated_gmm_roofline", "moe_gated_load_max_over_mean"}
    # the standing metrics that carry a list stay the other cells'
    assert not any(m.startswith(("moe_router", "moe_dispatch", "cca_mix",
                                 "moe_load", "moe_gmm", "moe_topk",
                                 "moe_held", "moe_shared", "ssm", "ssd"))
                   for m in by_name)
    read = lambda name: reducers.read(by_name[name], ctx,
                                      cell.metric_reader(name))
    parts = {name: read(name) for name in by_name
             if name.endswith("_time_pct") and name != "flash_time_pct"}
    # flash_dq ran nothing in this trace: left out, not a made-up 0
    assert parts.pop("flash_dq_time_pct") is None
    assert sum(parts.values()) == pytest.approx(100.0)
    assert parts["mlp_time_pct"] == pytest.approx(100 * 4.5 / busy)
    assert parts["attn_proj_time_pct"] == pytest.approx(100 * 1.0 / busy)
    assert parts["mla_latent_time_pct"] == pytest.approx(100 * 0.625 / busy)
    assert parts["attn_glue_time_pct"] == pytest.approx(100 * 0.5 / busy)
    assert parts["moe_gated_router_time_pct"] == pytest.approx(
        100 * 0.5 / busy)
    assert parts["moe_gated_dispatch_time_pct"] == pytest.approx(
        100 * 0.375 / busy)
    assert parts["moe_gated_shared_time_pct"] == pytest.approx(
        100 * 0.625 / busy)
    assert read("flash_time_pct") == pytest.approx(100 * 2.5 / busy)
    flash = cell.family.flops.flash_work(cell.config, 65536, 4096)
    assert read("flash_roofline") == pytest.approx(
        100 * flash["flops"] / 197e12 / 2.5)
    # no count from the program: nothing read
    assert read("moe_gated_gmm_roofline") is None
    assert read("moe_gated_load_max_over_mean") is None
    ctx["span_records"] = [{"name": "round/2/moe", "point": True, "attrs": {
        "live_share": 0.75, "held_share": 1.0, "max_over_mean": 1.5,
        "dropped": 0}}]
    work = cell.family.flops.moe_gated_gmm_work(cell.config, 65536, 4096,
                                                0.75)
    least = max(work["bytes"] / 819e9, work["flops"] / 197e12)
    assert read("moe_gated_gmm_roofline") == pytest.approx(100 * least / 3.0)
    assert read("moe_gated_load_max_over_mean") == 1.5


def test_a_program_without_the_family_reads_nothing_new():
    """The parent of this PR under this PR's benchmark files: no kernel, no
    scope and no event of the family's — every new reader answers ``None``
    and raises nothing."""
    cell = _real()
    empty = {"trace": Trace([], [], [("bench.round", 0.0, 1.0)]),
             "config": cell.config, "traffic": cell.traffic,
             "family": cell.family, "hlo_text": "", "span_records": [],
             "peaks": PEAKS, "rounds": 1, "tokens": 65536}
    for m in cell.per_layer():
        if m.get("workloads") == [REAL]:
            assert cell.metric_reader(m["name"])(empty) is None, m["name"]


def test_the_familys_reference_is_the_repos(glm_root):
    """The benchmark's own statement of the two kinds of layer against
    ``fedml_tpu/models/llm/glm_moe_lite_reference.py`` on the harness's
    weights (the repo's takes them in the published lane order): the loss
    and every adapter's gradient, float32 on both sides (1e-4 of a leaf's
    largest entry covers the order of summation)."""
    from fedml_tpu.models.llm import glm_moe_lite_reference

    cell = spec.Cell(CELL, root=glm_root)
    config, family = cell.config, cell.family
    ref = Reference(7, cell, "float32_highest")
    cfg = family.program.model_config(config, {"remat_policy": "none"})
    cfg = cfg.__class__(**{**cfg.__dict__, "dtype": jnp.float32})
    assert (cfg.head_dim, cfg.rotary_dim, cfg.expert_layers) == (16, 4, 2)
    made = weights.make_all(family.weights, config, 7)
    tree = {}
    for path, leaf in made.items():
        at = tree
        *parents, last = path.split("/")
        for key in parents:
            at = at.setdefault(key, {})
        at[last] = leaf
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, config["vocab_size"], (1, 32)))
    targets = jnp.roll(tokens, -1, axis=1)
    lora = {k: v for k, v in made.items() if family.weights.is_trainable(k)}
    assert len(lora) == 2 * 5 * 3

    def repo_loss(lora):
        merged = jax.tree_util.tree_map_with_path(
            lambda p, v: lora.get("/".join(str(k.key) for k in p), v), tree)
        return glm_moe_lite_reference.loss(
            cfg, glm_moe_lite_reference.published(cfg, merged), tokens,
            targets)

    want_loss, want = jax.value_and_grad(repo_loss)(lora)
    loss, got = ref.loss_and_grads(ref.lora, tokens, targets)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    for i, layer in enumerate(got):
        for k, g in layer.items():
            w = want[f"layer_{i}/{k}"]
            assert float(jnp.abs(w).max()) > 0, k
            np.testing.assert_allclose(
                g, w, rtol=0, atol=1e-4 * float(jnp.abs(w).max()),
                err_msg=f"layer_{i}/{k}")
    # the dense layer is one compiled program, the expert layers another
    assert ref.alike == [0, 1, 1]


@pytest.mark.parametrize("seed", [11, 2 ** 31 + 52])
def test_the_control_fails_and_bfloat16_passes(glm_root, seed):
    cell = spec.Cell(CELL, root=glm_root)
    want = bench_run.reference_round(cell, seed)
    fp8 = check.judge(check.numbers(
        bench_run.reference_round(cell, seed, precision="fp8"), want),
        cell.limits)
    assert fp8["correct"] is False, fp8
    bf16 = check.judge(check.numbers(
        bench_run.reference_round(cell, seed, precision="bfloat16"), want),
        cell.limits)
    assert bf16["correct"] is True, bf16


@pytest.mark.parametrize("breaker", [state_unchanged, half_left_out],
                         ids=["state_unchanged", "half_left_out"])
def test_a_broken_round_of_a_tiny_glm_is_not_correct(glm_root, monkeypatch,
                                                     breaker):
    """The timed path broken underneath: a state handed back as it was
    given, and half of the clients left out of the mean, both fail."""
    _broken_build(monkeypatch, breaker)
    cell = spec.Cell(CELL, root=glm_root)
    out = bench_run.measure(cell, 12, 0.1, False, [FakeDevice()], PEAKS)
    assert out["correct"] is False
    failed = {k for k, v in out["compared"].items() if not v["ok"]}
    assert failed == {"count", "grad", "grad2", "change"}, out["compared"]


def test_a_sound_run_of_a_tiny_glm_is_correct(glm_root, capsys):
    """``measure`` after the look for a chip: the program's tree is the
    layout the family states, the round runs, the reference follows it."""
    from fedml_tpu.telemetry import get_tracer, reset_tracer

    reset_tracer()   # a broken round's events (half the assignments) too
    cell = spec.Cell(CELL, root=glm_root)
    out = bench_run.measure(cell, 12, 0.2, False, [FakeDevice()], PEAKS)
    print(json.dumps(out["compared"]))
    assert out["correct"], out["compared"]
    assert out["failed"] == 0 and out["compared"]["count"]["value"] == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    prog = next(x for x in lines if x.get("phase") == "program")
    assert prog["counters"]["llm/fused_round.n_signatures"] == 1
    assert prog["counters"]["llm/fused_round.fallback_calls"] == 0
    events = [r["attrs"] for r in get_tracer().records()
              if r.get("point") and r["name"].endswith("/moe")]
    assert len(events) == out["attempted"] + 1
    for e in events:
        assert e["dropped"] == 0 and e["layers"] == 2
        assert (e["experts"], e["held"], e["top_k"]) == (8, 8, 2)
        assert e["assignments"] == e["tokens"] * 2
        assert e["held_share"] == 1.0 and e["max_over_mean"] >= 1.0
        # 32 tokens a step, two choices each (64 rows), each of the 8
        # runs padded by up to 7 rows, in whole tiles of 8
        assert e["capacity_rows"] == 120
    assert cell.metric_reader("moe_gated_load_max_over_mean")(
        {"rounds": out["attempted"]}) >= 1.0
