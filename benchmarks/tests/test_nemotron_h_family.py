"""``families/nemotron_h``: its counts, its scopes, its reference against
the repo's own, and the whole run after the look for a chip, at tiny widths
on the CPU with the real pattern letters (``MEMEMEM*EME``: 5 Mamba, 5
expert, 1 attention layer; a quarter of the experts held, top-3 of 16; an
untied head)."""
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

REAL = "nemotron-3-super-120b-a12b.round-2k"
TINY = {
    "source": "test only", "model_type": "nemotron_h",
    "attention_bias": False, "chunk_size": 8, "conv_kernel": 4,
    "head_dim": 8, "hidden_size": 64,
    "hybrid_override_pattern": "MEMEMEM*EME", "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 8, "mamba_num_heads": 16, "mamba_proj_bias": False,
    "max_position_embeddings": 128, "mlp_bias": False,
    "moe_intermediate_size": 48, "moe_latent_size": 32,
    "moe_shared_expert_intermediate_size": 96, "n_group": 1, "n_groups": 2,
    "n_routed_experts": 4, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 8, "num_experts_per_tok": 3,
    "num_hidden_layers": 11, "num_key_value_heads": 2, "rope_theta": 10000,
    "routed_scaling_factor": 5, "ssm_state_size": 16,
    "tie_word_embeddings": False, "topk_group": 1, "use_conv_bias": True,
    "vocab_size": 256,
    "published": {"num_hidden_layers": 88, "n_routed_experts": 16,
                  "hybrid_override_pattern": "MEMEMEM*EME" * 8,
                  "vocab_size": 1024},
    "run": {"lora_rank": 4, "lora_alpha": 4.0,
            "lora_targets": ["q_proj", "k_proj", "v_proj", "o_proj",
                             "in_proj", "out_proj"],
            "base_dtype": "bfloat16", "compute_dtype": "bfloat16",
            "adapter_dtype": "float32", "use_flash_attention": True,
            "moe_block_rows": 8, "held_experts_first": 4}}
# from CPU readings of this tiny cell on 6 seeds, two past 2**31
# (calibrate.py --any-device; the real cell's limits are read on the chip at
# its own size): on five of them the program reads grad 0.0048-0.0134 and
# grad2 0.0036-0.0095, the reference in bfloat16 0.0036-0.0117 and
# 0.0023-0.0124; the fp8 control reads grad from 0.0551 and grad2 from 0.0197
# (it fails grad on every seed); half of the clients left out 0.20 and 0.32.
# The sixth seed (13) reads grad 0.058 for the program AND for the bfloat16
# reference: with 32 tokens a step and 3 of 16 experts a token, one token the
# bfloat16 stream routes otherwise than the float32 one is 3 % of a step (the
# routing hazard, PERF.md); the tests below use seeds that were read. change
# is not compared here: sound runs reach 0.0297, the control starts at 0.0387
TINY_LIMITS = {"limits": {"count": 0, "grad": 0.03, "grad2": 0.03}}
CELL = "tiny-nemotron.round-tiny"


@pytest.fixture()
def nemotron_root(tiny_root):
    """``conftest.tiny_root`` with a tiny nemotron_h configuration and its
    cell added, again by new files and new entries alone."""
    bench = os.path.join(tiny_root, "benchmarks")
    with open(os.path.join(bench, "configs", "tiny-nemotron.json"), "w") as f:
        json.dump(TINY, f)
    with open(os.path.join(bench, "limits", CELL + ".json"), "w") as f:
        json.dump(TINY_LIMITS, f)
    path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(path) as f:
        bm = json.load(f)
    bm["configs"].append({"name": "tiny-nemotron", "source": "test only",
                          "file": "benchmarks/configs/tiny-nemotron.json",
                          "reduced": sorted(TINY["published"]), "why": "test"})
    bm["workloads"].append({"name": CELL, "config": "tiny-nemotron",
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


def test_the_configuration_is_the_row_with_the_chips_share():
    cell = _real()
    reduced = ["num_hidden_layers", "hybrid_override_pattern",
               "n_routed_experts", "vocab_size"]
    assert cell.config_entry["reduced"] == reduced
    assert cell.entry["chips"] == 1 and cell.entry["traffic"] == "round-2k"
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog beside the guide here")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")
    assert cell.config_entry["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items() if cell.config[k] != v}
    assert changed == set(reduced)
    assert cell.config["published"] == {k: row["config"][k] for k in reduced}
    # a whole period in the model's own ratio, a quarter of the experts and
    # of the vocabulary, every width the row's
    pattern = row["config"]["hybrid_override_pattern"]
    assert cell.config["hybrid_override_pattern"] == pattern[:11]
    assert [pattern.count(k) for k in "ME*"] == [40, 40, 8]
    assert [pattern[:11].count(k) for k in "ME*"] == [5, 5, 1]
    assert cell.config["n_routed_experts"] * 4 == 512
    assert cell.config["vocab_size"] * 4 == 131072
    assert cell.traffic["seq_len"] == 2048
    assert cell.traffic["clients_per_round"] * cell.traffic["local_steps"] == 16
    assert set(cell.limits["limits"]) <= {"count", "grad", "grad2", "change"}


def test_model_flops_count_the_held_experts_a_token_chose():
    cell = _real()
    config, flops = cell.config, cell.family.flops
    base = weights.param_count(cell.family.weights, config)
    assert base == config["parameters"] == 4648163712
    tokens = 32768
    got = flops.model_flops(config, tokens, 2048)
    assert got["total"] == (got["base"] + got["lora"] + got["attention"]
                            + got["scan"])
    p = flops.active_matmul_params(config)
    assert p["one_expert"] == 2 * 1024 * 2688
    assert flops.held_share(config) == 0.25
    # the leaves a token is multiplied by: all but the norm scales, the
    # small Mamba leaves, the selection bias, the embedding rows it only
    # looks up, and all but 5.5 of the 128 held experts of a layer
    small = 11 * 4096 + 4096 + 5 * (10240 + 3 * 128 + 8192) + 5 * 512 \
        + 32768 * 4096
    active = base - small - 5 * (128 - 5.5) * p["one_expert"]
    assert 5 * p["M"] + 5 * p["E"] + p["*"] + p["head"] == active
    assert got["base"] == 4 * active * tokens
    assert active == pytest.approx(1.15e9, rel=0.01)
    # the held routed products, the shared expert and the latent
    # projections; the Mamba projections
    assert 5 * (5.5 * p["one_expert"] + 2 * 4096 * 5376 + 2 * 4096 * 1024) \
        == pytest.approx(0.41e9, rel=0.02)
    assert 5 * p["M"] == pytest.approx(0.55e9, rel=0.01)
    lora = flops.lora_params(config)
    assert weights.param_count(cell.family.weights, config, trainable=True) \
        == 5 * lora["M"] + lora["*"] == 3196928
    work = flops.moe_held_gmm_work(config, tokens, 2048)
    assert work["flops"] == 4 * 5 * 2 * 1024 * 2688 * tokens * 22 / 4
    # bound by reading the weights: least time by bytes is over twice that
    # by operations
    assert work["bytes"] / 819e9 > 2 * work["flops"] / 197e12
    # fewer live experts and a smaller share held are less work
    less = flops.moe_held_gmm_work(config, tokens, 2048, 0.5, 0.2)
    assert less["bytes"] < work["bytes"] and less["flops"] < work["flops"]
    scan = flops.ssd_work(config, tokens, 2048)
    assert scan["flops"] == got["scan"] == 3 * 5 * 128 * 64 * 128 * 5 * tokens
    assert scan["bytes"] / 819e9 > scan["flops"] / 197e12   # bytes bind
    flash = flops.flash_work(config, tokens, 2048)
    assert flash["flops"] == 9 * 2048 * 2048 * 128 * 32 * 16


R = "jit(fed_round)/while/body/closed_call/while/body/closed_call"
FWD = R + "/jvp(NemotronHForCausalLM)"
BWD = R + "/transpose(jvp(NemotronHForCausalLM))"
CALL = 'custom-call(%a), custom_call_target="tpu_custom_call"'
# (instruction, the rest of its line, op_name, seconds, the part it lands in)
OPS = [
    ("moe_gmm.3", CALL, FWD + "/layer_1/moe/experts/moe_gmm/pallas_call",
     2.0, "mlp"),
    ("moe_gmm_t.4", CALL, BWD + "/layer_1/moe/experts/moe_gmm_t/pallas_call",
     1.0, "mlp"),
    ("fusion.5", "fusion(%a), kind=kLoop",
     FWD + "/layer_1/moe/experts/square", 0.5, "mlp"),
    ("fusion.6", "fusion(%a), kind=kOutput",
     FWD + "/layer_1/moe/router/dot_general", 0.25, "moe_topk_router"),
    ("sort.7", "sort(%a)", FWD + "/layer_1/moe/router/top_k", 0.25,
     "moe_topk_router"),
    ("fusion.8", "fusion(%a), kind=kLoop",
     FWD + "/layer_1/moe/moe_dispatch/gather", 0.25, "moe_topk_dispatch"),
    ("fusion.9", "fusion(%a), kind=kLoop",
     BWD + "/layer_1/moe/moe_combine/mul", 0.125, "moe_topk_dispatch"),
    ("fusion.10", "fusion(%a), kind=kOutput",
     FWD + "/layer_1/moe/shared/up_proj/dot_general", 0.5, "moe_shared"),
    ("fusion.11", "fusion(%a), kind=kOutput",
     BWD + "/layer_1/moe/latent_in/dot_general", 0.125, "moe_shared"),
    ("fusion.12", "fusion(%a), kind=kOutput",
     FWD + "/layer_0/mamba/in_proj/dot_general", 1.0, "ssm_proj"),
    ("fusion.13", "fusion(%a), kind=kOutput",
     BWD + "/layer_0/mamba/out_proj/dot_general", 0.5, "ssm_proj"),
    ("fusion.14", "fusion(%a), kind=kLoop",
     FWD + "/layer_0/mamba/jit(_ssd)/ssd/checkpoint/exp", 0.5, "ssm_scan"),
    ("fusion.15", "fusion(%a), kind=kOutput",
     BWD + "/layer_0/mamba/jit(_ssd)/ssd/checkpoint/rematted_computation/"
     "bcghqs,bcsghp->bcqghp/dot_general", 1.0, "ssm_scan"),
    ("while.16", "while(%a)", BWD + "/layer_0/mamba/jit(_ssd)", 0.25,
     "ssm_scan"),
    ("fusion.17", "fusion(%a), kind=kLoop",
     FWD + "/layer_0/mamba/ssm_conv/mul", 0.25, "ssm_glue"),
    ("fusion.18", "fusion(%a), kind=kLoop",
     BWD + "/layer_0/mamba/ssm_gate_norm/rsqrt", 0.25, "ssm_glue"),
    ("fusion.19", "fusion(%a), kind=kOutput",
     FWD + "/layer_7/attn/q_proj/dot_general", 0.125, "attn_proj"),
    ("flash_fwd.20", CALL, FWD + "/layer_7/attn/flash_fwd/pallas_call", 1.0,
     "flash_fwd"),
    ("fusion.21", "fusion(%a), kind=kLoop",
     FWD + "/layer_7/attn/attn_layout/transpose", 0.25, "attn_glue"),
    ("fusion.22", "fusion(%a), kind=kLoop",
     FWD + "/layer_2/input_norm/mul", 0.25, "norm"),
    ("fusion.23", "fusion(%a), kind=kOutput", FWD + "/lm_head/dot_general",
     1.0, "head_loss"),
    ("fusion.24", "fusion(%a), kind=kLoop", R + "/optimizer/add", 0.125,
     "round_glue"),
    ("slice-done.25", "async-done(%s)", None, 0.0625, "unattributed"),
]
HLO = "HloModule jit_fed_round\n\nENTRY %main (a: f32[8]) -> f32[8] {\n" + \
    "".join(f"  %{name} = f32[8]{{0}} {rest}"
            + (f', metadata={{op_name="{op}"}}' if op else "") + "\n"
            for name, rest, op, _, _ in OPS) + "}\n"
NEW_PARTS = ["ssm_proj", "ssm_scan", "ssm_glue", "moe_topk_router",
             "moe_topk_dispatch", "moe_shared"]


def _trace():
    at, events = 0.0, []
    for name, rest, _, seconds, _ in OPS:
        events.append((f"%{name} = f32[8]{{0}} {rest}", at, at + seconds, 0))
        at += seconds
    return Trace(events, [], [("bench.round", 0.0, at)]), at


def test_the_familys_parts_and_the_ten_that_stand_sum_to_busy_time():
    family = spec.Family("nemotron_h")
    assert [p for p, _ in family.scopes] == ["mlp"] + NEW_PARTS
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
    assert set(got) <= standing | set(NEW_PARTS)
    assert sum(got.values()) == pytest.approx(busy)


def test_every_new_metric_reads_its_part_its_kernel_or_its_event():
    cell = _real()
    trace, busy = _trace()
    ctx = {"trace": trace, "config": cell.config, "traffic": cell.traffic,
           "family": cell.family, "hlo_text": HLO, "span_records": [],
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
           "rounds": 1, "tokens": 32768}
    by_name = {m["name"]: m for m in cell.per_layer()}
    new = {m for m, v in by_name.items() if v.get("workloads") == [REAL]}
    assert new == {"ssm_proj_time_pct", "ssm_scan_time_pct",
                   "ssm_glue_time_pct", "ssd_roofline",
                   "moe_topk_router_time_pct", "moe_topk_dispatch_time_pct",
                   "moe_shared_time_pct", "moe_held_gmm_roofline",
                   "moe_held_load_max_over_mean"}
    # the standing metrics that carry a list stay the other cell's
    assert not any(m.startswith(("moe_router", "moe_dispatch", "cca_mix",
                                 "moe_load", "moe_gmm")) for m in by_name)
    read = lambda name: reducers.read(by_name[name], ctx,
                                      cell.metric_reader(name))
    assert read("mlp_time_pct") == pytest.approx(100 * 3.5 / busy)
    assert read("ssm_proj_time_pct") == pytest.approx(100 * 1.5 / busy)
    assert read("ssm_scan_time_pct") == pytest.approx(100 * 1.75 / busy)
    assert read("ssm_glue_time_pct") == pytest.approx(100 * 0.5 / busy)
    assert read("moe_topk_router_time_pct") == pytest.approx(100 * 0.5 / busy)
    assert read("moe_topk_dispatch_time_pct") == pytest.approx(
        100 * 0.375 / busy)
    assert read("moe_shared_time_pct") == pytest.approx(100 * 0.625 / busy)
    assert read("attn_proj_time_pct") == pytest.approx(100 * 0.125 / busy)
    assert read("norm_time_pct") == pytest.approx(100 * 0.25 / busy)
    scan = cell.family.flops.ssd_work(cell.config, 32768, 2048)
    assert read("ssd_roofline") == pytest.approx(
        100 * scan["bytes"] / 819e9 / 1.75)
    # no count from the program: nothing read
    assert read("moe_held_gmm_roofline") is None
    assert read("moe_held_load_max_over_mean") is None
    ctx["span_records"] = [{"name": "round/2/moe", "point": True, "attrs": {
        "live_share": 0.75, "held_share": 0.26, "max_over_mean": 2.5,
        "dropped": 0}}]
    work = cell.family.flops.moe_held_gmm_work(cell.config, 32768, 2048,
                                               0.75, 0.26)
    assert read("moe_held_gmm_roofline") == pytest.approx(
        100 * work["bytes"] / 819e9 / 3.0)
    assert read("moe_held_load_max_over_mean") == 2.5


def test_the_familys_reference_is_the_repos(nemotron_root):
    """The benchmark's own statement of the three mixers against
    ``fedml_tpu/models/llm/nemotron_h_reference.py`` on the harness's
    weights: the loss and every adapter's gradient, float32 on both sides
    (1e-4 of a leaf's largest entry covers the order of summation)."""
    from fedml_tpu.models.llm import nemotron_h_reference

    cell = spec.Cell(CELL, root=nemotron_root)
    config, family = cell.config, cell.family
    ref = Reference(7, cell, "float32_highest")
    cfg = family.program.model_config(config, {"remat_policy": "none"})
    cfg = cfg.__class__(**{**cfg.__dict__, "dtype": jnp.float32})
    assert cfg.experts_total == 16 and cfg.held_experts_first == 4
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
    assert len(lora) == 2 * (5 * 2 + 4)

    def repo_loss(lora):
        merged = jax.tree_util.tree_map_with_path(
            lambda p, v: lora.get("/".join(str(k.key) for k in p), v), tree)
        return nemotron_h_reference.loss(cfg, merged, tokens, targets)

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


@pytest.mark.parametrize("seed", [11, 2 ** 31 + 52])
def test_the_control_fails_and_bfloat16_passes(nemotron_root, seed):
    cell = spec.Cell(CELL, root=nemotron_root)
    want = bench_run.reference_round(cell, seed)
    fp8 = check.judge(check.numbers(
        bench_run.reference_round(cell, seed, precision="fp8"), want),
        cell.limits)
    assert fp8["correct"] is False, fp8
    bf16 = check.judge(check.numbers(
        bench_run.reference_round(cell, seed, precision="bfloat16"), want),
        cell.limits)
    assert bf16["correct"] is True, bf16


def test_a_sound_run_of_a_tiny_nemotron_is_correct(nemotron_root, capsys):
    """``measure`` after the look for a chip: the program's tree is the
    layout the family states, the round runs, the reference follows it."""
    cell = spec.Cell(CELL, root=nemotron_root)
    out = bench_run.measure(cell, 12, 0.2, False, [FakeDevice()], PEAKS)
    print(json.dumps(out["compared"]))
    assert out["correct"], out["compared"]
    assert out["failed"] == 0 and out["compared"]["count"]["value"] == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    prog = next(x for x in lines if x.get("phase") == "program")
    assert prog["counters"]["llm/fused_round.n_signatures"] == 1
    assert prog["counters"]["llm/fused_round.fallback_calls"] == 0
    from fedml_tpu.telemetry import get_tracer

    events = [r["attrs"] for r in get_tracer().records()
              if r.get("point") and r["name"].endswith("/moe")]
    assert len(events) >= out["attempted"] + 1
    for e in events:
        assert e["dropped"] == 0 and e["layers"] == 5
        assert (e["experts"], e["held"], e["top_k"]) == (16, 4, 3)
        assert e["assignments"] == e["tokens"] * 3
        assert 0 < e["held_share"] < 1 and e["max_over_mean"] >= 1.0
        # 32 tokens a step, every choice held (96 rows), each of the 4
        # runs padded by up to 7 rows, in whole tiles of 8
        assert e["capacity_rows"] == 128
    assert cell.metric_reader("moe_held_load_max_over_mean")(
        {"rounds": out["attempted"]}) >= 1.0
