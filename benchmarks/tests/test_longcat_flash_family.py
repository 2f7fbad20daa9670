"""``families/longcat_flash``: its counts, its scopes, its reference against
the repo's own, and the whole run after the look for a chip, at tiny widths
on the CPU with the row's shape (double layers: two latent attentions with
value heads narrower than the score lanes, two dense FFNs, the shortcut
expert branch; a quarter of the FFN experts held, top-3 of 16 FFN and 8
identity experts; an untied head)."""
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

REAL = "longcat-flash-chat.round-4k-remat"
TINY = {
    "source": "test only", "model_type": "longcat_flash",
    "attention_bias": False, "attention_method": "MLA",
    "expert_ffn_hidden_size": 16, "ffn_hidden_size": 128, "hidden_size": 64,
    "kv_lora_rank": 16, "max_position_embeddings": 128,
    "mla_scale_kv_lora": True, "mla_scale_q_lora": True, "moe_topk": 3,
    "n_routed_experts": 4, "num_attention_heads": 4, "num_layers": 2,
    "num_hidden_layers": 2, "q_lora_rank": 48, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "rms_norm_eps": 1e-05, "rope_theta": 10000000,
    "routed_scaling_factor": 6, "v_head_dim": 16, "vocab_size": 256,
    "zero_expert_num": 8, "zero_expert_type": "identity",
    "published": {"num_layers": 28, "n_routed_experts": 16,
                  "vocab_size": 1024},
    "run": {"lora_rank": 4, "lora_alpha": 4.0,
            "lora_targets": ["q_a_proj", "q_b_proj", "kv_a_proj",
                             "kv_b_proj", "o_proj"],
            "base_dtype": "bfloat16", "compute_dtype": "bfloat16",
            "adapter_dtype": "float32", "use_flash_attention": True,
            "moe_block_rows": 8, "held_experts_first": 4}}
# from CPU readings of this tiny cell (calibrate.py --any-device) on 6
# seeds, three past 2**31: the program reads grad 0.0052-0.0110 and grad2
# 0.0022-0.0141, the reference in bfloat16 0.0029-0.0070 and 0.0019-0.0076;
# the fp8 control reads grad from 0.0506 and grad2 from 0.0362; half of the
# clients left out grad from 0.23, change from 0.38. Each limit sits near
# the geometric mean of its two readings; change is not compared: sound
# runs reach 0.0154, the control starts at 0.0369
TINY_LIMITS = {"limits": {"count": 0, "grad": 0.024, "grad2": 0.022}}
CELL = "tiny-longcat-flash.round-tiny"


@pytest.fixture()
def longcat_root(tiny_root):
    """``conftest.tiny_root`` with a tiny longcat_flash configuration and
    its cell added, again by new files and new entries alone."""
    bench = os.path.join(tiny_root, "benchmarks")
    with open(os.path.join(bench, "configs", "tiny-longcat-flash.json"),
              "w") as f:
        json.dump(TINY, f)
    with open(os.path.join(bench, "limits", CELL + ".json"), "w") as f:
        json.dump(TINY_LIMITS, f)
    path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(path) as f:
        bm = json.load(f)
    bm["configs"].append({"name": "tiny-longcat-flash", "source": "test only",
                          "file": "benchmarks/configs/tiny-longcat-flash.json",
                          "reduced": sorted(TINY["published"]),
                          "why": "test"})
    bm["workloads"].append({"name": CELL, "config": "tiny-longcat-flash",
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
    reduced = ["num_layers", "n_routed_experts", "vocab_size"]
    assert cell.config_entry["reduced"] == reduced
    assert cell.entry["chips"] == 1
    assert cell.entry["traffic"] == "round-4k-remat"
    assert cell.config["published"] == {"num_layers": 28,
                                        "n_routed_experts": 512,
                                        "vocab_size": 131072}
    # a seventh of the double layers (the harness's name for them too), a
    # 32nd of the FFN experts, an 8th of the vocabulary; every width the
    # row's
    assert cell.config["num_hidden_layers"] == cell.config["num_layers"] == 4
    assert cell.config["n_routed_experts"] * 32 == 512
    assert cell.config["vocab_size"] * 8 == 131072
    cfg = cell.family.program.model_config(cell.config, cell.traffic)
    assert (cfg.head_dim, cfg.v_head_dim, cfg.rotary_dim) == (192, 128, 64)
    assert cfg.experts_total + cfg.zero_expert_num == 768
    assert cfg.mla_scales == pytest.approx((2.0, 12 ** 0.5))
    assert cfg.remat_policy == "full" and cfg.moe_block_rows == 64
    assert cell.traffic["seq_len"] == 4096
    assert cell.traffic["clients_per_round"] * cell.traffic["local_steps"] == 16
    base = dict(json.load(open(os.path.join(
        spec.BENCH_DIR, "traffic", "round-4k.json"))))
    assert {k: v for k, v in cell.traffic.items()
            if k not in ("remat_policy", "remat_decision")} == {
        k: v for k, v in base.items()
        if k not in ("remat_policy", "remat_decision")}
    assert set(cell.limits["limits"]) <= {"loss", "count", "grad", "grad2",
                                          "change"}


def test_model_flops_count_the_held_experts_a_token_chose():
    cell = _real()
    config, flops = cell.config, cell.family.flops
    base = weights.param_count(cell.family.weights, config)
    assert base == config["parameters"] == 5172749312
    assert weights.param_count(cell.family.weights, config,
                               trainable=True) \
        == 4 * flops.lora_params_per_layer(config) == 7610368
    tokens = 65536
    got = flops.model_flops(config, tokens, 4096)
    assert got["total"] == got["base"] + got["lora"] + got["attention"]
    p = flops.active_matmul_params(config)
    assert p["one_expert"] == 3 * 6144 * 2048
    # the leaves a token is multiplied by: all but the norms, the
    # selection bias, the embedding rows it only looks up, and all but
    # 12 x 16 / 768 = 0.25 of the 16 held experts of a double layer
    norms = 4 * (4 * 6144 + 2 * (1536 + 512)) + 6144
    small = norms + 4 * 768 + 16384 * 6144
    active = base - small - 4 * (16 - 0.25) * p["one_expert"]
    assert 4 * p["double_layer"] + p["head"] == active
    assert got["base"] == 4 * active * tokens
    assert got["attention"] == 3 * 4096 * 4096 * (192 + 128) * 64 * 8 * 16
    flash = flops.flash_work(config, tokens, 4096)
    # 5 products over 192 lanes and 4 over 128, at half of the square
    assert flash["flops"] == (5 * 192 + 4 * 128) * 4096 * 4096 * 64 * 8 * 16
    assert flash["flops"] / 197e12 > flash["bytes"] / 819e9  # compute binds
    work = flops.moe_held_gmm_work(config, tokens, 4096)
    # 16 of 768 outputs held: 0.25 rows a token, three products each way
    assert work["flops"] == 4 * 6 * 2 * 6144 * 2048 * tokens * 12 * 16 / 768
    # 64 rows an expert a step: bound by reading the held experts' weights
    assert work["bytes"] / 819e9 > 3 * work["flops"] / 197e12
    less = flops.moe_held_gmm_work(config, tokens, 4096, 0.5, 0.01)
    assert less["bytes"] < work["bytes"] and less["flops"] < work["flops"]


R = "jit(fed_round)/while/body/closed_call/while/body/closed_call"
FWD = R + "/jvp(LongcatFlashForCausalLM)"
BWD = R + "/transpose(jvp(LongcatFlashForCausalLM))"
CALL = 'custom-call(%a), custom_call_target="tpu_custom_call"'
# (instruction, the rest of its line, op_name, seconds, the part it lands in)
OPS = [
    ("flash_fwd.1", CALL, FWD + "/layer_0/attn_0/flash_fwd/pallas_call", 1.0,
     "flash_fwd"),
    ("flash_bwd_dq.2", CALL,
     BWD + "/layer_0/attn_1/flash_bwd_dq/pallas_call", 0.5, "flash_dq"),
    ("flash_bwd_dkv.3", CALL,
     BWD + "/layer_1/attn_0/flash_bwd_dkv/pallas_call", 0.5, "flash_dkv"),
    ("moe_gmm.4", CALL, FWD + "/layer_1/moe/experts/moe_gmm/pallas_call",
     0.5, "scmoe"),
    ("moe_gmm_t.5", CALL, BWD + "/layer_1/moe/experts/moe_gmm_t/pallas_call",
     0.25, "scmoe"),
    ("fusion.6", "fusion(%a), kind=kOutput",
     FWD + "/layer_1/moe/router/dot_general", 0.25, "scmoe"),
    ("fusion.7", "fusion(%a), kind=kLoop",
     BWD + "/layer_2/moe/moe_combine/mul", 0.25, "scmoe"),
    ("fusion.8", "fusion(%a), kind=kLoop",
     FWD + "/layer_3/moe/zero_experts/mul", 0.125, "scmoe"),
    ("fusion.9", "fusion(%a), kind=kOutput",
     FWD + "/layer_0/attn_1/q_b_proj/dot_general", 0.5, "attn_proj"),
    ("fusion.10", "fusion(%a), kind=kOutput",
     BWD + "/layer_2/attn_0/kv_b_proj/dot_general", 0.25, "attn_proj"),
    ("fusion.11", "fusion(%a), kind=kLoop",
     FWD + "/layer_0/attn_0/kv_a_norm/rsqrt", 0.125, "attn_glue"),
    ("fusion.12", "fusion(%a), kind=kLoop",
     BWD + "/layer_0/attn_1/mla_assemble/concatenate", 0.125, "attn_glue"),
    ("fusion.13", "fusion(%a), kind=kOutput",
     FWD + "/layer_0/mlp_0/up_proj/dot_general", 2.0, "mlp"),
    ("fusion.14", "fusion(%a), kind=kOutput",
     BWD + "/layer_3/mlp_1/down_proj/dot_general", 1.0, "mlp"),
    ("fusion.15", "fusion(%a), kind=kLoop",
     FWD + "/layer_2/post_attn_norm_1/mul", 0.25, "norm"),
    ("fusion.16", "fusion(%a), kind=kLoop",
     FWD + "/layer_1/input_norm_0/mul", 0.125, "norm"),
    ("fusion.17", "fusion(%a), kind=kOutput", FWD + "/lm_head/dot_general",
     1.0, "head_loss"),
    ("fusion.18", "fusion(%a), kind=kLoop", R + "/optimizer/add", 0.125,
     "round_glue"),
    ("slice-done.19", "async-done(%s)", None, 0.0625, "unattributed"),
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


def test_the_familys_parts_and_the_standing_ones_sum_to_busy_time():
    family = spec.Family("longcat_flash")
    assert [p for p, _ in family.scopes] == [
        "flash_fwd", "flash_dq", "flash_dkv", "scmoe", "scmoe", "attn_proj",
        "attn_glue", "mlp", "norm"]
    trace, busy = _trace()
    got = scopes.seconds_by_part(trace, scopes.instruction_op_names(HLO),
                                 scopes.load_rules(family.scopes))
    want = {}
    for _, _, _, seconds, part in OPS:
        want[part] = want.get(part, 0.0) + seconds
    assert got == pytest.approx(want)
    assert sum(got.values()) == pytest.approx(busy)


def test_every_new_metric_reads_its_part_or_its_events():
    cell = _real()
    trace, busy = _trace()
    events = [{"point": True, "name": f"round/{r}/moe",
               "attrs": {"buffer_live_share": share}}
              for r, share in ((2, 0.02), (3, 0.03))]
    ctx = {"trace": trace, "config": cell.config, "traffic": cell.traffic,
           "family": cell.family, "hlo_text": HLO, "span_records": events,
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
           "rounds": 2, "tokens": 65536}
    by_name = {m["name"]: m for m in cell.per_layer()}
    new = {m for m, v in by_name.items() if v.get("workloads") == [REAL]}
    assert new == {"scmoe_time_pct", "moe_buffer_live_share"}
    # no metric of another family's fork reads this cell
    assert not any(m.startswith(("moe_gated", "moe_held", "moe_topk", "ssm_",
                                 "ssd_", "cca_", "mla_", "gdn_"))
                   for m in by_name)
    read = lambda name: reducers.read(by_name[name], ctx,
                                      cell.metric_reader(name))
    assert read("scmoe_time_pct") == pytest.approx(100 * 1.375 / busy)
    assert read("attn_proj_time_pct") == pytest.approx(100 * 0.75 / busy)
    assert read("attn_glue_time_pct") == pytest.approx(100 * 0.25 / busy)
    assert read("mlp_time_pct") == pytest.approx(100 * 3.0 / busy)
    assert read("norm_time_pct") == pytest.approx(100 * 0.375 / busy)
    assert read("moe_buffer_live_share") == pytest.approx(0.025)
    # the parent's program leaves no such field: nothing, not an error
    ctx["span_records"] = [{"point": True, "name": "round/2/moe",
                            "attrs": {"held_share": 0.02}}]
    assert read("moe_buffer_live_share") is None


def test_the_held_experts_roofline_reads_this_family():
    """``moe_held_gmm_roofline`` does not list this cell in its
    ``workloads``, but its reader finds the family's ``moe_held_gmm_work``
    and the kernels by name: once membership comes from the family, it
    reads here."""
    from benchmarks.metrics import moe_held_gmm_roofline
    cell = _real()
    trace, _ = _trace()
    events = [{"point": True, "name": f"round/{r}/moe",
               "attrs": {"held_share": 0.02, "live_share": 1.0}}
              for r in (2, 3)]
    ctx = {"trace": trace, "config": cell.config, "traffic": cell.traffic,
           "family": cell.family, "span_records": events,
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
           "rounds": 2, "tokens": 65536}
    work = cell.family.flops.moe_held_gmm_work(cell.config, 65536, 4096,
                                               live_share=1.0, held=0.02)
    least = max(work["flops"] / 197e12, work["bytes"] / 819e9)
    # the trace's moe_gmm and moe_gmm_t: 0.5 + 0.25 s
    assert moe_held_gmm_roofline.read(ctx) == pytest.approx(
        100 * least / 0.75)


def _tree(made):
    tree = {}
    for path, leaf in made.items():
        at = tree
        *parents, last = path.split("/")
        for key in parents:
            at = at.setdefault(key, {})
        at[last] = leaf
    return tree


def test_the_familys_reference_is_the_repos(longcat_root):
    """The benchmark's own statement of a double layer against
    ``fedml_tpu/models/llm/longcat_flash_reference.py`` on the harness's
    weights (moved to the published lane order for the repo's): the loss
    and every adapter's gradient, float32 on both sides (1e-4 of a leaf's
    largest entry covers the order of summation: the attention in query
    blocks, the sorted experts walked by tile against the loop with a
    mask)."""
    from fedml_tpu.models.llm import longcat_flash_reference

    cell = spec.Cell(CELL, root=longcat_root)
    config, family = cell.config, cell.family
    ref = Reference(7, cell, "float32_highest")
    cfg = family.program.model_config(config, {"remat_policy": "none"})
    cfg = cfg.__class__(**{**cfg.__dict__, "dtype": jnp.float32})
    assert cfg.experts_total == 16 and cfg.held_experts_first == 4
    made = weights.make_all(family.weights, config, 7)
    tree = _tree(made)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, config["vocab_size"], (1, 32)))
    targets = jnp.roll(tokens, -1, axis=1)
    lora = {k: v for k, v in made.items() if family.weights.is_trainable(k)}
    assert len(lora) == 2 * 2 * 5 * 2

    def repo_loss(lora):
        merged = jax.tree_util.tree_map_with_path(
            lambda p, v: lora.get("/".join(str(k.key) for k in p), v), tree)
        return longcat_flash_reference.loss(
            cfg, longcat_flash_reference.published(cfg, merged), tokens,
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


@pytest.mark.parametrize("seed", [11, 2 ** 31 + 52])
def test_the_control_fails_and_bfloat16_passes(longcat_root, seed):
    cell = spec.Cell(CELL, root=longcat_root)
    want = bench_run.reference_round(cell, seed)
    fp8 = check.judge(check.numbers(
        bench_run.reference_round(cell, seed, precision="fp8"), want),
        cell.limits)
    assert fp8["correct"] is False, fp8
    bf16 = check.judge(check.numbers(
        bench_run.reference_round(cell, seed, precision="bfloat16"), want),
        cell.limits)
    assert bf16["correct"] is True, bf16


def test_an_unchanged_state_and_half_the_clients_fail(longcat_root):
    """What a broken round would hand back: the adapters it was given
    (nothing trained) and the mean over half of the clients."""
    cell = spec.Cell(CELL, root=longcat_root)
    want = bench_run.reference_round(cell, 11)
    still = dict(want, lora=want["init_lora"])
    assert check.judge(check.numbers(still, want),
                       {"limits": {"change": 0.5}})["correct"] is False
    half = bench_run.reference_round(cell, 11, fault="half_clients")
    assert check.judge(check.numbers(half, want),
                       cell.limits)["correct"] is False


def test_a_sound_run_of_a_tiny_longcat_flash_is_correct(longcat_root, capsys):
    """``measure`` after the look for a chip: the program's tree is the
    layout the family states, the round runs, the reference follows it,
    and the ``round/<n>/moe`` event carries the identity experts' share and
    the buffer's live share."""
    from fedml_tpu import telemetry

    # the tracer is the process's: start and leave it empty, so that no
    # other test of this process reads this cell's events as its own
    telemetry.reset_tracer()
    cell = spec.Cell(CELL, root=longcat_root)
    out = bench_run.measure(cell, 12, 0.2, False, [FakeDevice()], PEAKS)
    print(json.dumps(out["compared"]))
    assert out["correct"], out["compared"]
    assert out["failed"] == 0 and out["compared"]["count"]["value"] == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    prog = next(x for x in lines if x.get("phase") == "program")
    assert prog["counters"]["llm/fused_round.n_signatures"] == 1
    assert prog["counters"]["llm/fused_round.fallback_calls"] == 0
    events = [r["attrs"] for r in telemetry.get_tracer().records()
              if r.get("point") and r["name"].endswith("/moe")]
    assert len(events) >= out["attempted"] + 1
    for e in events:
        assert e["dropped"] == 0 and e["layers"] == 2
        # the router's 16 FFN and 8 identity outputs, 4 FFN experts held
        assert (e["experts"], e["held"], e["top_k"]) == (24, 4, 3)
        assert e["assignments"] == e["tokens"] * 3
        assert 0 < e["held_share"] < 1 and 0 < e["zero_share"] < 1
        # 32 tokens a step, every choice that could be held (96 rows),
        # each of the 4 runs padded by up to 7 rows, in whole tiles of 8
        assert e["capacity_rows"] == 128
        assert 0 < e["buffer_live_share"] <= 1
    telemetry.reset_tracer()
