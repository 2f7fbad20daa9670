"""``families/qwen3_next``: its counts, its scopes, its reference against
the repo's own, and the whole run after the look for a chip, at tiny widths
on the CPU with the real layer pattern (one whole period: three Gated
DeltaNet layers, then attention; two value heads a key head; a quarter of
the experts held, top-3 of 16; an untied head)."""
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

REAL = "qwen3-next-80b-a3b-instruct.round-4k"
TINY = {
    "source": "test only", "model_type": "qwen3_next",
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 16,
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 160,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 32,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_value_head_dim": 32, "max_position_embeddings": 128,
    "mlp_only_layers": [], "moe_intermediate_size": 16,
    "norm_topk_prob": True, "num_attention_heads": 8, "num_experts": 4,
    "num_experts_per_tok": 3, "num_hidden_layers": 4,
    "num_key_value_heads": 1, "partial_rotary_factor": 0.25,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 16, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 256,
    "published": {"num_hidden_layers": 48, "num_experts": 16,
                  "vocab_size": 1024},
    "run": {"lora_rank": 4, "lora_alpha": 4.0,
            "lora_targets": ["q_proj", "k_proj", "v_proj", "o_proj",
                             "in_proj_qkvz", "out_proj"],
            "base_dtype": "bfloat16", "compute_dtype": "bfloat16",
            "adapter_dtype": "float32", "use_flash_attention": True,
            "moe_block_rows": 8, "held_experts_first": 4}}
# from CPU readings of this tiny cell (calibrate.py --any-device) on 9
# seeds, three past 2**31: the program reads grad 0.0020-0.0326 and grad2
# 0.0021-0.0236, the reference in bfloat16 0.0017-0.0127 and 0.0011-0.0085;
# the fp8 control reads grad from 0.0416 (0.042-0.227) and grad2 from 0.0259;
# half of the clients left out grad from 0.19, change from 0.27. Heads of 32
# lanes: at 8 the program and the bfloat16 reference read grad up to 0.19
# (a head's delta-rule output, RMS-normed over 8 lanes, is the least well
# conditioned number there). grad carries the control; change is not
# compared: sound runs reach 0.0175, the control starts at 0.0171
TINY_LIMITS = {"limits": {"count": 0, "grad": 0.037, "grad2": 0.03}}
CELL = "tiny-qwen3-next.round-tiny"


@pytest.fixture()
def qwen_root(tiny_root):
    """``conftest.tiny_root`` with a tiny qwen3_next configuration and its
    cell added, again by new files and new entries alone."""
    bench = os.path.join(tiny_root, "benchmarks")
    with open(os.path.join(bench, "configs", "tiny-qwen3-next.json"),
              "w") as f:
        json.dump(TINY, f)
    with open(os.path.join(bench, "limits", CELL + ".json"), "w") as f:
        json.dump(TINY_LIMITS, f)
    path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(path) as f:
        bm = json.load(f)
    bm["configs"].append({"name": "tiny-qwen3-next", "source": "test only",
                          "file": "benchmarks/configs/tiny-qwen3-next.json",
                          "reduced": sorted(TINY["published"]),
                          "why": "test"})
    bm["workloads"].append({"name": CELL, "config": "tiny-qwen3-next",
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
    reduced = ["num_hidden_layers", "num_experts", "vocab_size"]
    assert cell.config_entry["reduced"] == reduced
    assert cell.entry["chips"] == 1 and cell.entry["traffic"] == "round-4k"
    assert set(cell.config["published"]) == set(reduced)
    assert cell.config["published"] == {"num_hidden_layers": 48,
                                        "num_experts": 512,
                                        "vocab_size": 151936}
    # one whole period of the pattern, a quarter of the experts and whole
    # 128-row tiles of a quarter of the vocabulary, every width the row's
    cfg = cell.family.program.model_config(cell.config, cell.traffic)
    assert [cfg.is_linear(i) for i in range(4)] == [True, True, True, False]
    assert cell.config["num_experts"] * 4 == 512
    assert cell.config["vocab_size"] == 297 * 128
    assert (cfg.rotary_dim, cfg.key_dim, cfg.value_dim, cfg.conv_dim) == (
        64, 2048, 4096, 8192)
    assert cell.traffic["seq_len"] == 4096
    assert cell.traffic["clients_per_round"] * cell.traffic["local_steps"] == 16
    assert set(cell.limits["limits"]) <= {"loss", "count", "grad", "grad2",
                                          "change"}


def test_model_flops_count_the_held_experts_a_token_chose():
    cell = _real()
    config, flops = cell.config, cell.family.flops
    base = weights.param_count(cell.family.weights, config)
    assert base == config["parameters"] == 1911548992
    tokens = 65536
    got = flops.model_flops(config, tokens, 4096)
    assert got["total"] == (got["base"] + got["lora"] + got["attention"]
                            + got["scan"])
    p = flops.active_matmul_params(config)
    assert p["one_expert"] == 3 * 2048 * 512
    assert flops.held_share(config) == 0.25
    # the leaves a token is multiplied by: all but the norms, the small
    # Gated DeltaNet leaves, the embedding rows it only looks up, and all
    # but 2.5 of the 128 held experts of a layer
    small = 8 * 2048 + 2048 + 3 * (32 + 32 + 128) + 2 * 256 + 38016 * 2048
    active = base - small - 4 * (128 - 2.5) * p["one_expert"]
    assert 3 * p["linear"] + p["full"] + 4 * p["moe"] + p["head"] == active
    assert got["base"] == 4 * active * tokens
    # three mixers of 33.7e6, one of 27.3e6, four expert layers of 12.1e6
    # and the head's 77.9e6
    assert active == pytest.approx(0.2545e9, rel=0.001)
    lora = flops.lora_params(config)
    assert weights.param_count(cell.family.weights, config, trainable=True) \
        == 3 * lora["linear"] + lora["full"] == 1327104
    scan = flops.gdn_work(config, tokens, 4096)
    assert scan["flops"] == got["scan"] == 3 * 7 * 32 * 128 * 128 * 3 * tokens
    assert scan["bytes"] / 819e9 > scan["flops"] / 197e12   # bytes bind
    flash = flops.flash_work(config, tokens, 4096)
    assert flash["flops"] == 9 * 4096 * 4096 * 256 * 16 * 16


R = "jit(fed_round)/while/body/closed_call/while/body/closed_call"
FWD = R + "/jvp(Qwen3NextForCausalLM)"
BWD = R + "/transpose(jvp(Qwen3NextForCausalLM))"
CALL = 'custom-call(%a), custom_call_target="tpu_custom_call"'
SCAN = "/layer_0/linear_attn/jit(_gated_delta)/gated_delta/checkpoint"
# (instruction, the rest of its line, op_name, seconds, the part it lands in)
OPS = [
    ("moe_gmm.3", CALL, FWD + "/layer_1/moe/experts/moe_gmm/pallas_call",
     2.0, "mlp"),
    ("moe_gmm_t.4", CALL, BWD + "/layer_1/moe/experts/moe_gmm_t/pallas_call",
     1.0, "mlp"),
    ("fusion.5", "fusion(%a), kind=kOutput",
     FWD + "/layer_1/moe/router/dot_general", 0.25, "mlp"),
    ("fusion.6", "fusion(%a), kind=kLoop",
     FWD + "/layer_1/moe/moe_dispatch/gather", 0.25, "mlp"),
    ("fusion.7", "fusion(%a), kind=kOutput",
     FWD + "/layer_1/moe/shared/up_proj/dot_general", 0.5, "mlp"),
    ("fusion.8", "fusion(%a), kind=kLoop",
     BWD + "/layer_1/moe/shared_gate/mul", 0.125, "mlp"),
    ("fusion.9", "fusion(%a), kind=kOutput",
     FWD + "/layer_0/linear_attn/in_proj_qkvz/dot_general", 1.0,
     "gdn_proj"),
    ("fusion.10", "fusion(%a), kind=kOutput",
     BWD + "/layer_0/linear_attn/out_proj/dot_general", 0.5, "gdn_proj"),
    ("fusion.11", "fusion(%a), kind=kOutput",
     FWD + "/layer_2/linear_attn/in_proj_ba/dot_general", 0.125, "gdn_proj"),
    ("fusion.12", "fusion(%a), kind=kLoop", FWD + SCAN + "/exp", 0.5,
     "gdn_scan"),
    ("fusion.13", "fusion(%a), kind=kOutput",
     BWD + SCAN + "/rematted_computation/bchij,bchjd->bchid/dot_general",
     1.0, "gdn_scan"),
    ("while.14", "while(%a)",
     BWD + "/layer_0/linear_attn/jit(_gated_delta)", 0.25, "gdn_scan"),
    ("fusion.15", "fusion(%a), kind=kLoop",
     FWD + "/layer_0/linear_attn/gdn_conv/mul", 0.25, "gdn_glue"),
    ("fusion.16", "fusion(%a), kind=kLoop",
     BWD + "/layer_0/linear_attn/gdn_gate_norm/rsqrt", 0.25, "gdn_glue"),
    ("fusion.17", "fusion(%a), kind=kLoop",
     FWD + "/layer_1/linear_attn/gdn_gates/rsqrt", 0.125, "gdn_glue"),
    ("fusion.18", "fusion(%a), kind=kOutput",
     FWD + "/layer_3/attn/q_proj/dot_general", 0.125, "attn_proj"),
    ("flash_fwd.19", CALL, FWD + "/layer_3/attn/flash_fwd/pallas_call", 1.0,
     "flash_fwd"),
    ("fusion.20", "fusion(%a), kind=kLoop",
     FWD + "/layer_3/attn/q_norm/rsqrt", 0.125, "attn_glue"),
    ("fusion.21", "fusion(%a), kind=kLoop",
     BWD + "/layer_3/attn/attn_gate/logistic", 0.125, "attn_glue"),
    ("fusion.22", "fusion(%a), kind=kLoop",
     FWD + "/layer_2/post_attn_norm/mul", 0.25, "norm"),
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
NEW_PARTS = ["gdn_proj", "gdn_scan", "gdn_glue"]


def _trace():
    at, events = 0.0, []
    for name, rest, _, seconds, _ in OPS:
        events.append((f"%{name} = f32[8]{{0}} {rest}", at, at + seconds, 0))
        at += seconds
    return Trace(events, [], [("bench.round", 0.0, at)]), at


def test_the_familys_parts_and_the_ten_that_stand_sum_to_busy_time():
    family = spec.Family("qwen3_next")
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


def test_every_new_metric_reads_its_part_or_its_work():
    cell = _real()
    trace, busy = _trace()
    ctx = {"trace": trace, "config": cell.config, "traffic": cell.traffic,
           "family": cell.family, "hlo_text": HLO, "span_records": [],
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
           "rounds": 1, "tokens": 65536}
    by_name = {m["name"]: m for m in cell.per_layer()}
    new = {m for m, v in by_name.items() if v.get("workloads") == [REAL]}
    assert new == {"gdn_proj_time_pct", "gdn_scan_time_pct",
                   "gdn_glue_time_pct", "gdn_roofline"}
    # no MoE metric of another family's fork reads this cell
    assert not any(m.startswith(("moe_", "ssm_", "ssd_", "cca_", "mla_"))
                   for m in by_name)
    read = lambda name: reducers.read(by_name[name], ctx,
                                      cell.metric_reader(name))
    assert read("mlp_time_pct") == pytest.approx(100 * 4.125 / busy)
    assert read("gdn_proj_time_pct") == pytest.approx(100 * 1.625 / busy)
    assert read("gdn_scan_time_pct") == pytest.approx(100 * 1.75 / busy)
    assert read("gdn_glue_time_pct") == pytest.approx(100 * 0.625 / busy)
    assert read("attn_glue_time_pct") == pytest.approx(100 * 0.25 / busy)
    assert read("norm_time_pct") == pytest.approx(100 * 0.25 / busy)
    scan = cell.family.flops.gdn_work(cell.config, 65536, 4096)
    assert read("gdn_roofline") == pytest.approx(
        100 * scan["bytes"] / 819e9 / 1.75)


def test_the_familys_reference_is_the_repos(qwen_root):
    """The benchmark's own statement of both mixers and the expert layer
    against ``fedml_tpu/models/llm/qwen3_next_reference.py`` on the
    harness's weights: the loss and every adapter's gradient, float32 on
    both sides (1e-4 of a leaf's largest entry covers the order of
    summation: the sequential loop in blocks against one scan, the attention
    in query blocks, the sorted experts against the loop with a mask)."""
    from fedml_tpu.models.llm import qwen3_next_reference

    cell = spec.Cell(CELL, root=qwen_root)
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
    assert len(lora) == 2 * (3 * 2 + 4)

    def repo_loss(lora):
        merged = jax.tree_util.tree_map_with_path(
            lambda p, v: lora.get("/".join(str(k.key) for k in p), v), tree)
        return qwen3_next_reference.loss(cfg, merged, tokens, targets)

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
def test_the_control_fails_and_bfloat16_passes(qwen_root, seed):
    cell = spec.Cell(CELL, root=qwen_root)
    want = bench_run.reference_round(cell, seed)
    fp8 = check.judge(check.numbers(
        bench_run.reference_round(cell, seed, precision="fp8"), want),
        cell.limits)
    assert fp8["correct"] is False, fp8
    bf16 = check.judge(check.numbers(
        bench_run.reference_round(cell, seed, precision="bfloat16"), want),
        cell.limits)
    assert bf16["correct"] is True, bf16


def test_an_unchanged_state_and_half_the_clients_fail(qwen_root):
    """What a broken round would hand back: the adapters it was given
    (nothing trained) and the mean over half of the clients."""
    cell = spec.Cell(CELL, root=qwen_root)
    want = bench_run.reference_round(cell, 11)
    still = dict(want, lora=want["init_lora"])
    assert check.judge(check.numbers(still, want),
                       {"limits": {"change": 0.5}})["correct"] is False
    half = bench_run.reference_round(cell, 11, fault="half_clients")
    assert check.judge(check.numbers(half, want),
                       cell.limits)["correct"] is False


def test_a_sound_run_of_a_tiny_qwen3_next_is_correct(qwen_root, capsys):
    """``measure`` after the look for a chip: the program's tree is the
    layout the family states, the round runs, the reference follows it."""
    cell = spec.Cell(CELL, root=qwen_root)
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
        assert e["dropped"] == 0 and e["layers"] == 4
        assert (e["experts"], e["held"], e["top_k"]) == (16, 4, 3)
        assert e["assignments"] == e["tokens"] * 3
        assert 0 < e["held_share"] < 1 and e["max_over_mean"] >= 1.0
        # 32 tokens a step, every choice held (96 rows), each of the 4
        # runs padded by up to 7 rows, in whole tiles of 8
        assert e["capacity_rows"] == 128
