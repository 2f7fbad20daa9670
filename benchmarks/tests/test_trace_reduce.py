"""The reduction on a hand-made event list, every number worked out by hand."""
import pytest

from benchmarks.harness import reducers, trace_reduce
from benchmarks.harness.trace_reduce import Trace

# one device, seconds. Two round spans [0, 4] and [5, 9]; the window is
# [0, 9]. Ops: a [0.5, 1.5], b [1.0, 2.0] (overlap a -> union [0.5, 2.0]),
# flash fwd [2.0, 2.5], gap [2.5, 3.0] inside the module, flash dq
# [3.0, 3.5]; second round: c [5.5, 8.5]. One op before the window
# [-1, -0.5] that must not count.
OPS = [("fusion.1", 0.5, 1.5, 0), ("fusion.2", 1.0, 2.0, 0),
       ("flash_fwd.3", 2.0, 2.5, 0), ("flash_dq.4", 3.0, 3.5, 0),
       ("fusion.9", 5.5, 8.5, 0), ("fusion.7", -1.0, -0.5, 0)]
MODULES = [("jit_fed_round(1)", 0.5, 3.5, 0), ("jit_fed_round(1)", 5.5, 8.5, 0),
           ("jit_other", -1.0, -0.5, 0)]
SPANS = [("bench.round", 0.0, 4.0), ("bench.round", 5.0, 9.0)]


@pytest.fixture()
def trace():
    return Trace(OPS, MODULES, SPANS)


def test_union_and_busy(trace):
    assert trace.window == (0.0, 9.0)
    assert trace_reduce.union([(1, 2), (0.5, 1.5), (3, 4)]) == [[0.5, 2], [3, 4]]
    # [0.5, 2.5] + [3.0, 3.5] + [5.5, 8.5] = 2.0 + 0.5 + 3.0
    assert trace_reduce.busy_s(trace) == pytest.approx(5.5)


def test_idle_share_and_kernels(trace):
    ctx = {"trace": trace}
    assert reducers.device_idle_pct(ctx, {}) == pytest.approx(100 * 3.5 / 9)
    assert trace_reduce.kernel_s(trace, r"^flash_(fwd|dq)") == pytest.approx(1.0)
    assert reducers.kernel_time_pct(
        ctx, {"kernel_pattern": r"^flash_"}) == pytest.approx(100 / 5.5)
    assert reducers.kernel_time_pct(ctx, {"kernel_pattern": "absent"}) is None


def test_module_and_host_share(trace):
    ctx = {"trace": trace}
    assert reducers.module_ms(
        ctx, {"module_pattern": "fed_round"}) == pytest.approx(3000.0)
    assert reducers.module_ms(ctx, {"module_pattern": "absent"}) is None
    # span 1: 4.0 - 2.5 busy = 1.5; span 2: 4.0 - 3.0 = 1.0
    assert reducers.span_minus_busy_ms(
        ctx, {"span": "bench.round"}) == pytest.approx(1250.0)


def test_gaps_are_named_by_the_spans(trace):
    gaps = dict(trace_reduce.idle_gaps(trace, "fed_round"))
    assert gaps["round.before_program"] == pytest.approx(0.5 + 0.5)
    assert gaps["round.inside_program"] == pytest.approx(0.5)
    assert gaps["round.after_program"] == pytest.approx(0.5 + 0.5)
    assert gaps["between_rounds"] == pytest.approx(1.0)
    assert sum(gaps.values()) == pytest.approx(9.0 - 5.5)


def test_top_ops_fold_digits(trace):
    top = dict(trace_reduce.top_ops(trace))
    assert top["fusion.N"] == pytest.approx(1.0 + 1.0 + 3.0)
    assert top["flash_fwd.N"] == pytest.approx(0.5)


def test_rooflines_and_mfu_from_shapes(trace):
    config = {"hidden_size": 8, "intermediate_size": 16, "num_hidden_layers": 1,
              "num_attention_heads": 2, "num_key_value_heads": 1,
              "vocab_size": 10,
              "run": {"lora_rank": 2, "lora_targets": ["q_proj"]}}
    ctx = {"trace": trace, "config": config, "traffic": {"seq_len": 4},
           "tokens": 8, "peaks": {"bf16_flops_per_s": 1e3,
                                  "hbm_bytes_per_s": 1e3}}
    # base per layer 8*8*2 + 8*4*2 + 3*8*16 = 576, head 80: 4*656*8 = 20992
    # lora q: 2*(8+8) = 32 -> 6*32*8 = 1536; attention: 6 * (2*4*4*4/2) * 2
    # heads = 768, x 2 rows = 1536
    from benchmarks.harness import flops
    work = flops.model_flops(config, 8, 4)
    assert work == {"base": 20992, "lora": 1536, "attention": 1536,
                    "total": 24064}
    assert reducers.mfu_pct(ctx, {}) == pytest.approx(100 * 24064 / 9 / 1e3)
    flash = flops.flash_work(config, 8, 4)
    assert flash["flops"] == 9 * 64 * 2 * 2  # 9 products, 2 heads, 2 rows
    q, kv, stat = 4 * 2 * 4 * 2, 4 * 1 * 4 * 2, 4 * 2 * 4
    per_row = (2 * q + 2 * kv + stat) + (3 * q + 2 * kv + 2 * stat) + (
        4 * q + 2 * kv + 2 * stat)
    assert flash["bytes"] == 2 * per_row
    least = max(flash["flops"], flash["bytes"]) / 1e3
    assert reducers.kernel_roofline_pct(
        ctx, {"kernel_pattern": "^flash_", "work": "flash_work"}
    ) == pytest.approx(100 * least / 1.0)
    assert reducers.kernel_roofline_pct(
        ctx, {"kernel_pattern": "absent", "work": "flash_work"}) is None
