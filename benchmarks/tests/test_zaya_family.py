"""``families/zaya``: its counts, its scopes, its reference against the
repo's own, and the whole run after the look for a chip, at tiny widths on
the CPU (8/2 heads, 16 experts top-1, two taps and two, rope on half of a
head, tied head: the ratios of the published row)."""
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

from .test_rehearsal import PEAKS, FakeDevice

TINY_ZAYA = {
    "source": "test only", "model_type": "zaya", "attention_bias": False,
    "cca_time0": 2, "cca_time1": 2, "head_dim": 8, "hidden_size": 128,
    "layer_types": ["hybrid"] * 4, "max_position_embeddings": 128,
    "moe_intermediate_size": 128, "num_attention_heads": 8,
    "num_experts": 16, "num_experts_per_tok": 1, "num_hidden_layers": 2,
    "num_key_value_heads": 2, "rms_norm_eps": 1e-05,
    "rope_parameters": {"hybrid": {"partial_rotary_factor": 0.5,
                                   "rope_theta": 10000.0}},
    "router_hidden_size": 16, "tie_word_embeddings": True, "vocab_size": 256,
    "published": {"num_hidden_layers": 4},
    "run": {"lora_rank": 4, "lora_alpha": 4.0,
            "lora_targets": ["q_proj", "k_proj", "v_proj", "v_prev_proj",
                             "o_proj"],
            "base_dtype": "bfloat16", "compute_dtype": "bfloat16",
            "adapter_dtype": "float32", "use_flash_attention": True,
            "moe_block_rows": 8}}
# from CPU readings of this tiny cell on 6 seeds, two past 2**31 (calibrate.py
# --any-device; the real cell's limits are read on the chip at its own size):
# the program reads grad 0.0017-0.0092 and grad2 0.0018-0.0087, the reference
# in bfloat16 0.0014-0.0061 and 0.0008-0.0052; the fp8 control reads grad
# from 0.0231 and grad2 from 0.0164; half of the clients left out 0.27 and
# 0.28. change is not compared here: sound runs reach 0.0158 (with 32 tokens a
# step, one token the bfloat16 stream sends to another expert than the
# float32 one is 3 % of a step), the control starts at 0.0136
TINY_ZAYA_LIMITS = {"limits": {"count": 0, "grad": 0.015, "grad2": 0.012}}
CELL = "tiny-zaya.round-tiny"


@pytest.fixture()
def zaya_root(tiny_root):
    """``conftest.tiny_root`` with a tiny zaya configuration and its cell
    added, again by new files and new entries alone."""
    bench = os.path.join(tiny_root, "benchmarks")
    with open(os.path.join(bench, "configs", "tiny-zaya.json"), "w") as f:
        json.dump(TINY_ZAYA, f)
    with open(os.path.join(bench, "limits", CELL + ".json"), "w") as f:
        json.dump(TINY_ZAYA_LIMITS, f)
    path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(path) as f:
        bm = json.load(f)
    bm["configs"].append({"name": "tiny-zaya", "source": "test only",
                          "file": "benchmarks/configs/tiny-zaya.json",
                          "reduced": ["num_hidden_layers"], "why": "test"})
    bm["workloads"].append({"name": CELL, "config": "tiny-zaya",
                            "traffic": "round-tiny", "chips": 1,
                            "why": "test"})
    for m in bm["per_layer"]:
        if m.get("workloads") == ["zaya1-8b.round-mid"]:
            m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(bm, f)
    return tiny_root


def _real():
    return spec.Cell("zaya1-8b.round-mid")


def test_the_configuration_is_the_row_cut_in_depth_alone():
    cell = _real()
    assert cell.config_entry["reduced"] == ["num_hidden_layers"]
    assert cell.config["published"] == {"num_hidden_layers": 40}
    assert cell.config["num_hidden_layers"] == 24
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog beside the guide here")
    with open(path) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "ZAYA1-8B")
    assert cell.config_entry["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items() if cell.config[k] != v}
    assert changed == {"num_hidden_layers"}
    assert cell.traffic["seq_len"] == 1024
    assert set(cell.limits["limits"]) <= {"count", "grad", "grad2", "change"}


def test_model_flops_count_one_expert_of_sixteen():
    cell = _real()
    config, flops = cell.config, cell.family.flops
    base = weights.param_count(cell.family.weights, config)
    assert base == pytest.approx(5.519e9, rel=1e-3)
    tokens = 16384
    got = flops.model_flops(config, tokens, 1024)
    assert got["total"] == got["base"] + got["lora"] + got["attention"]
    p = flops.active_matmul_params(config)
    assert p["one_expert"] == 3 * 2048 * 2048
    layers, experts = config["num_hidden_layers"], config["num_experts"]
    # the leaves a token is multiplied by: all but the norm scales, the
    # biases, the temperatures and gammas, and 15 of every 16 experts
    small = layers * (2 * 2048 + 2 * 1280 + 2 + 2 * 256) + 2048
    active = base - small - layers * (experts - 1) * p["one_expert"]
    assert layers * p["layer"] + p["head"] == active
    assert got["base"] == 4 * active * tokens
    # by all base parameters the count would read over five times the work
    assert 5 * got["total"] < 4 * base * tokens
    assert got["total"] / tokens == pytest.approx(4.13e9, rel=0.01)
    assert weights.param_count(cell.family.weights, config, trainable=True) \
        == layers * flops.lora_params_per_layer(config)
    work = flops.moe_gmm_work(config, tokens, 1024)
    assert work["flops"] == 6 * layers * 2 * 2048 * 2048 * tokens
    # bound by reading the weights: least time by bytes is 4x that by ops
    assert work["bytes"] / 819e9 > 3 * work["flops"] / 197e12


R = "jit(fed_round)/while/body/closed_call/while/body/closed_call"
FWD, BWD = R + "/jvp(ZayaForCausalLM)", R + "/transpose(jvp(ZayaForCausalLM))"
# (instruction, the rest of its line, op_name, seconds, the part it lands in)
OPS = [
    ("moe_gmm.3", 'custom-call(%a), custom_call_target="tpu_custom_call"',
     FWD + "/layer_0/moe/experts/moe_gmm/pallas_call", 2.0, "mlp"),
    ("moe_gmm_t.4", 'custom-call(%a), custom_call_target="tpu_custom_call"',
     BWD + "/layer_0/moe/experts/moe_gmm_t/pallas_call", 1.0, "mlp"),
    ("fusion.5", "fusion(%a), kind=kLoop",
     FWD + "/layer_0/moe/experts/mul", 0.5, "mlp"),
    ("fusion.6", "fusion(%a), kind=kOutput",
     FWD + "/layer_0/moe/router_mlp/router/dot_general", 0.25, "moe_router"),
    ("fusion.7", "fusion(%a), kind=kLoop",
     FWD + "/layer_0/moe/moe_dispatch/gather", 0.25, "moe_dispatch"),
    ("fusion.8", "fusion(%a), kind=kLoop",
     BWD + "/layer_0/moe/moe_combine/mul", 0.125, "moe_dispatch"),
    ("fusion.9", "fusion(%a), kind=kLoop",
     FWD + "/layer_1/attn/cca_mix/add", 0.5, "cca_mix"),
    ("fusion.10", "fusion(%a), kind=kOutput",
     FWD + "/layer_1/attn/v_prev_proj/dot_general", 0.125, "attn_proj"),
    ("fusion.11", "fusion(%a), kind=kOutput",
     BWD + "/layer_1/attn/q_proj/dot_general", 0.125, "attn_proj"),
    ("flash_fwd.12", 'custom-call(%a), custom_call_target="tpu_custom_call"',
     FWD + "/layer_1/attn/flash_fwd/pallas_call", 1.0, "flash_fwd"),
    ("fusion.13", "fusion(%a), kind=kLoop",
     FWD + "/layer_1/attn/rope/mul", 0.25, "attn_glue"),
    ("fusion.14", "fusion(%a), kind=kLoop",
     FWD + "/layer_1/post_attn_norm/mul", 0.25, "norm"),
    ("fusion.15", "fusion(%a), kind=kOutput", FWD + "/lm_head/dot_general",
     1.0, "head_loss"),
    ("fusion.16", "fusion(%a), kind=kLoop", R + "/optimizer/add", 0.125,
     "round_glue"),
    ("slice-done.17", "async-done(%s)", None, 0.0625, "unattributed"),
]
HLO = "HloModule jit_fed_round\n\nENTRY %main (a: f32[8]) -> f32[8] {\n" + \
    "".join(f"  %{name} = f32[8]{{0}} {rest}"
            + (f', metadata={{op_name="{op}"}}' if op else "") + "\n"
            for name, rest, op, _, _ in OPS) + "}\n"


def _trace():
    at, events = 0.0, []
    for name, rest, _, seconds, _ in OPS:
        events.append((f"%{name} = f32[8]{{0}} {rest}", at, at + seconds, 0))
        at += seconds
    return Trace(events, [], [("bench.round", 0.0, at)]), at


def test_zayas_parts_and_the_ten_that_stand_sum_to_busy_time():
    family = spec.Family("zaya")
    assert [p for p, _ in family.scopes] == [
        "mlp", "moe_router", "moe_dispatch", "cca_mix", "attn_proj"]
    trace, busy = _trace()
    got = scopes.seconds_by_part(trace, scopes.instruction_op_names(HLO),
                                 scopes.load_rules(family.scopes))
    want = {}
    for _, _, _, seconds, part in OPS:
        want[part] = want.get(part, 0.0) + seconds
    assert got == pytest.approx(want)
    standing = {"flash_fwd", "flash_dq", "flash_dkv", "attn_proj",
                "attn_glue", "mlp", "norm", "head_loss", "round_glue",
                "unattributed"}
    assert set(got) <= standing | {"moe_router", "moe_dispatch", "cca_mix"}
    assert sum(got.values()) == pytest.approx(busy)
    # without the family's pairs the shared ones book the router, the
    # dispatch and the fifth projection's neighbours elsewhere
    shared = scopes.seconds_by_part(trace, scopes.instruction_op_names(HLO),
                                    scopes.load_rules())
    assert shared["mlp"] == pytest.approx(
        want["mlp"] + want["moe_router"] + want["moe_dispatch"])


def test_a_moe_gmm_call_is_the_mlps_and_no_flash_metrics():
    cell = _real()
    trace, busy = _trace()
    ctx = {"trace": trace, "config": cell.config, "traffic": cell.traffic,
           "family": cell.family, "hlo_text": HLO, "span_records": [],
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
           "rounds": 1, "tokens": 16384}
    by_name = {m["name"]: m for m in cell.per_layer()}
    read = lambda name: reducers.read(by_name[name], ctx,
                                      cell.metric_reader(name))
    assert read("mlp_time_pct") == pytest.approx(100 * 3.5 / busy)
    assert read("flash_time_pct") == pytest.approx(100 * 1.0 / busy)
    assert read("flash_fwd_time_pct") == pytest.approx(100 * 1.0 / busy)
    assert read("flash_dq_time_pct") is None
    assert read("moe_router_time_pct") == pytest.approx(100 * 0.25 / busy)
    assert read("moe_dispatch_time_pct") == pytest.approx(100 * 0.375 / busy)
    assert read("cca_mix_time_pct") == pytest.approx(100 * 0.5 / busy)
    # the program counted three quarters of the experts live: the least
    # bytes are those experts' matrices, over the 3 s of the two kernels
    assert read("moe_gmm_roofline") is None     # no count, nothing read
    ctx["span_records"] = [{"name": "round/2/moe", "point": True, "attrs": {
        "live_share": 0.75, "max_over_mean": 3.0, "dropped": 0}}]
    work = cell.family.flops.moe_gmm_work(cell.config, 16384, 1024, 0.75)
    assert work["bytes"] < cell.family.flops.moe_gmm_work(
        cell.config, 16384, 1024)["bytes"]
    assert read("moe_gmm_roofline") == pytest.approx(
        100 * work["bytes"] / 819e9 / 3.0)
    assert read("moe_load_max_over_mean") == 3.0
    # the flash roofline divides by the flash kernel's second alone
    flash = cell.family.flops.flash_work(cell.config, 16384, 1024)
    assert read("flash_roofline") == pytest.approx(
        100 * max(flash["flops"] / 197e12, flash["bytes"] / 819e9) / 1.0)


def test_the_load_is_read_from_the_programs_events():
    cell = _real()
    reader = cell.metric_reader("moe_load_max_over_mean")
    event = lambda n, v: {"name": f"round/{n}/moe", "point": True,
                          "attrs": {"max_over_mean": v, "dropped": 0}}
    records = [event(1, 9.0), {"name": "round/2/run", "duration_ms": 1.0},
               event(2, 1.5), event(3, 2.5)]
    assert reader({"span_records": records, "rounds": 2}) == 2.0
    # a program without routed experts (the parent) leaves nothing to read
    assert reader({"span_records": records[1:2], "rounds": 2}) is None


def test_the_familys_reference_is_the_repos(zaya_root):
    """The benchmark's own statement of steps 1-9 against ``fedml_tpu/
    models/llm/zaya_reference.py`` on the harness's weights: the loss and
    every adapter's gradient, float32 on both sides (1e-4 of a leaf's
    largest entry covers the order of summation), and the same tokens sent
    to the same experts."""
    from fedml_tpu.models.llm import zaya_reference

    cell = spec.Cell(CELL, root=zaya_root)
    config, family = cell.config, cell.family
    ref = Reference(7, cell, "float32_highest")
    cfg = family.program.model_config(config, {"remat_policy": "none"})
    cfg = cfg.__class__(**{**cfg.__dict__, "dtype": jnp.float32})
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

    def repo_loss(lora):
        merged = jax.tree_util.tree_map_with_path(
            lambda p, v: lora.get("/".join(str(k.key) for k in p), v), tree)
        return zaya_reference.loss(cfg, merged, tokens, targets)

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


@pytest.mark.parametrize("seed", [41, 2 ** 31 + 42])
def test_the_control_fails_and_bfloat16_passes(zaya_root, seed):
    cell = spec.Cell(CELL, root=zaya_root)
    want = bench_run.reference_round(cell, seed)
    fp8 = check.judge(check.numbers(
        bench_run.reference_round(cell, seed, precision="fp8"), want),
        cell.limits)
    assert fp8["correct"] is False, fp8
    bf16 = check.judge(check.numbers(
        bench_run.reference_round(cell, seed, precision="bfloat16"), want),
        cell.limits)
    assert bf16["correct"] is True, bf16


def test_a_sound_run_of_a_tiny_zaya_is_correct(zaya_root, capsys):
    """``measure`` after the look for a chip: the program's tree is the
    layout the family states, the round runs, the reference follows it."""
    cell = spec.Cell(CELL, root=zaya_root)
    out = bench_run.measure(cell, 3, 0.2, False, [FakeDevice()], PEAKS)
    print(json.dumps(out["compared"]))
    assert out["correct"], out["compared"]
    assert out["failed"] == 0 and out["compared"]["count"]["value"] == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    prog = next(x for x in lines if x.get("phase") == "program")
    assert prog["counters"]["llm/fused_round.n_signatures"] == 1
    from fedml_tpu.telemetry import get_tracer

    events = [r for r in get_tracer().records()
              if r.get("point") and r["name"].endswith("/moe")]
    assert len(events) >= out["attempted"] + 1
    assert all(e["attrs"]["dropped"] == 0 for e in events)
    assert cell.metric_reader("moe_load_max_over_mean")(
        {"rounds": out["attempted"]}) >= 1.0
