"""The two joins of ``harness/scopes.py`` on a hand-made trace, HLO text and
span records, every number worked out by hand."""
import pytest

from benchmarks.harness import scopes, spec, trace_reduce
from benchmarks.harness.trace_reduce import Trace

R = "jit(fed_round)/while/body/closed_call/while/body/closed_call"
FWD, BWD = R + "/jvp(LlamaForCausalLM)", R + "/transpose(jvp(LlamaForCausalLM))"

# the compiled module as ``as_text()`` prints it: instructions indented,
# computations at column 0. fusion.4 is a multi-output fusion the compiler
# left without an op_name (its root is a bare tuple); copy.6 and
# copy-done.8 have none either and take their producers'.
HLO = f'''HloModule jit_fed_round, entry_computation_layout={{()->f32[]}}

%fused_computation.4 (p0: f32[8,4]) -> (bf16[8,2], bf16[8,2]) {{
  %p0 = f32[8,4]{{1,0}} parameter(0)
  %split.1 = f32[8,2]{{1,0}} slice(%p0), slice={{[0:8], [0:2]}}, metadata={{op_name="{BWD}/layer_1/attn/rope/split"}}
  %mul.1 = f32[8,2]{{1,0}} multiply(%split.1, %split.1), metadata={{op_name="{BWD}/layer_1/attn/rope/mul"}}
  %mul.2 = f32[8,2]{{1,0}} multiply(%mul.1, %split.1), metadata={{op_name="{BWD}/layer_1/attn/rope/mul"}}
  %convert.1 = bf16[8,2]{{1,0}} convert(%mul.1)
  %convert.2 = bf16[8,2]{{1,0}} convert(%mul.2)
  ROOT %tuple.1 = (bf16[8,2]{{1,0}}, bf16[8,2]{{1,0}}) tuple(%convert.1, %convert.2)
}}

ENTRY %main.9 (a: bf16[8,4]) -> f32[] {{
  %a = bf16[8,4]{{1,0}} parameter(0)
  %fusion.1 = bf16[8,4]{{1,0:T(8,128)(2,1)}} fusion(%a), kind=kOutput, calls=%fused_computation.1, metadata={{op_name="{FWD}/layer_0/attn/q_proj/dot_general"}}
  %fusion.2 = bf16[8,4]{{1,0}} fusion(%fusion.1), kind=kOutput, calls=%fused_computation.2, metadata={{op_name="{BWD}/layer_0/mlp/gate_proj/dot_general"}}
  %fusion.3 = bf16[8,4]{{1,0}} fusion(%fusion.2), kind=kLoop, calls=%fused_computation.3, metadata={{op_name="{BWD}/rematted_computation/layer_0/input_norm/rsqrt"}}
  %fusion.4 = (bf16[8,2]{{1,0}}, bf16[8,2]{{1,0}}) fusion(%fusion.3), kind=kLoop, calls=%fused_computation.4
  %flash_fwd.5 = (bf16[1,2,8,4]{{3,2,1,0}}, f32[1,2,8,1]{{3,2,1,0}}) custom-call(%fusion.1, %fusion.2, %fusion.3), custom_call_target="tpu_custom_call", metadata={{op_name="{FWD}/layer_0/attn/flash_fwd/pallas_call"}}
  %copy.6 = bf16[8,4]{{0,1}} copy(%fusion.1), metadata={{op_name="{FWD}/layer_0/attn/flash_fwd/pallas_call"}}
  %fusion.7 = f32[8,16]{{1,0}} fusion(%fusion.3), kind=kOutput, calls=%fused_computation.7, metadata={{op_name="{FWD}/lm_head/dot_general"}}
  %copy-start.8 = (f32[8,16]{{1,0}}, f32[8,16]{{1,0}}, u32[]) copy-start(%fusion.7)
  %copy-done.8 = f32[8,16]{{1,0:S(1)}} copy-done(%copy-start.8)
  %fusion.10 = f32[4,2]{{1,0}} fusion(%a), kind=kLoop, calls=%fused_computation.10, metadata={{op_name="{R}/optimizer/add"}}
  %copy.11 = f32[4,2]{{1,0}} copy(%a), metadata={{op_name="jit(fed_round)/while/body/closed_call/while"}}
  %slice-start.12 = ((bf16[8,4]{{1,0}}), bf16[2,4]{{1,0}}, s32[]) async-start(%a), calls=%async_computation.12
  %slice-done.12 = bf16[2,4]{{1,0:S(1)}} async-done(%slice-start.12)
  ROOT %fusion.13 = f32[] fusion(%fusion.7), kind=kLoop, calls=%fused_computation.13, metadata={{op_name="{R}/transpose(jvp(loss))/mul"}}
}}
'''

# what the TPU trace calls an op: its instruction line without metadata.
# One device, seconds; the window is [0, 10] (two round brackets).
EVENTS = {
    "fusion.1": "%fusion.1 = bf16[8,4]{1,0:T(8,128)(2,1)} fusion(bf16[8,4]{1,0} %a), kind=kOutput, calls=%fused_computation.1",
    "fusion.2": "%fusion.2 = bf16[8,4]{1,0} fusion(bf16[8,4]{1,0} %fusion.1), kind=kOutput",
    "fusion.3": "%fusion.3 = bf16[8,4]{1,0} fusion(bf16[8,4]{1,0} %fusion.2), kind=kLoop",
    "fusion.4": "%fusion.4 = (bf16[8,2]{1,0}, bf16[8,2]{1,0}) fusion(bf16[8,4]{1,0} %fusion.3), kind=kLoop",
    "flash_fwd.5": '%flash_fwd.5 = (bf16[1,2,8,4]{3,2,1,0}, f32[1,2,8,1]{3,2,1,0}) custom-call(bf16[8,4]{1,0} %fusion.1), custom_call_target="tpu_custom_call"',
    "copy.6": "%copy.6 = bf16[8,4]{0,1} copy(bf16[8,4]{1,0} %fusion.1)",
    "fusion.7": "%fusion.7 = f32[8,16]{1,0} fusion(bf16[8,4]{1,0} %fusion.3), kind=kOutput",
    "copy-done.8": "%copy-done.8 = f32[8,16]{1,0:S(1)} copy-done(%copy-start.8)",
    "fusion.10": "%fusion.10 = f32[4,2]{1,0} fusion(bf16[8,4]{1,0} %a), kind=kLoop",
    "copy.11": "%copy.11 = f32[4,2]{1,0} copy(bf16[8,4]{1,0} %a)",
    "slice-done.12": "%slice-done.12 = bf16[2,4]{1,0:S(1)} async-done(%slice-start.12)",
    "fusion.13": "%fusion.13 = f32[] fusion(f32[8,16]{1,0} %fusion.7), kind=kLoop",
}
# (instruction, start, seconds, part it must land in)
TIMELINE = [
    ("fusion.1", 1.0, 1.0, "attn_proj"),
    ("fusion.2", 2.0, 2.0, "mlp"),            # a backward op
    ("fusion.3", 4.0, 0.5, "norm"),           # a rematted backward op
    ("fusion.4", 4.5, 0.25, "attn_glue"),     # named by its fused computation
    ("flash_fwd.5", 5.0, 1.0, "flash_fwd"),
    ("copy.6", 6.0, 0.125, "attn_glue"),      # the kernel's op_name, not the kernel
    ("fusion.7", 6.25, 0.5, "head_loss"),
    ("copy-done.8", 6.75, 0.125, "head_loss"),  # named by its producer
    ("fusion.10", 7.0, 0.25, "round_glue"),
    ("copy.11", 7.25, 0.125, "round_glue"),   # the scan's own carry copy
    ("slice-done.12", 7.5, 0.0625, "unattributed"),
    ("fusion.13", 8.0, 0.5, "head_loss"),
    ("fusion.1", -2.0, 1.0, None),            # before the window: not counted
]
OPS = [(EVENTS[k], s, s + d, 0) for k, s, d, _ in TIMELINE]
BRACKETS = [("bench.round", 0.0, 5.0), ("bench.round", 5.0, 10.0)]
BUSY_S = sum(d for _, s, d, _ in TIMELINE if s >= 0)
PARTS = ("flash_fwd", "flash_dq", "flash_dkv", "attn_proj", "attn_glue",
         "mlp", "norm", "head_loss", "round_glue", "unattributed")

# the program's own clock runs this far ahead of the trace's
OFFSET = -1790773696.4101918


def _round(n, start, run_ms, phases, trace_id="t"):
    """Span records as ``Tracer.end`` writes them, ``started`` on the
    program's clock; ``phases``: ``[(name, start on the trace clock, ms)]``."""
    run = {"name": f"round/{n}/run", "trace_id": trace_id + str(n),
           "span_id": f"run{n}", "parent_id": None,
           "started": start - OFFSET, "duration_ms": run_ms}
    kids = [{"name": f"round/{n}/{name}", "trace_id": run["trace_id"],
             "span_id": f"{name}{n}", "parent_id": run["span_id"],
             "started": s - OFFSET, "duration_ms": ms}
            for name, s, ms in phases]
    return kids + [run]  # a parent ends, and is recorded, after its children


# On the trace's clock. Round 2: run [0.005, 4.995] over sample [0.01, 0.11],
# stage [0.11, 0.51], dispatch [0.51, 1.5], wait [1.5, 4.99]. Round 3: run
# [5.005, 9.995] over stage [5.01, 5.5], dispatch [5.5, 6.2], wait [6.2, 9.99].
RECORDS = (
    _round(1, -3.0, 900.0, [("wait", -2.9, 800.0)])  # the warm-up round
    + _round(2, 0.005, 4990.0, [("sample", 0.01, 100.0), ("stage", 0.11, 400.0),
                                ("dispatch", 0.51, 990.0), ("wait", 1.5, 3490.0)])
    + _round(3, 5.005, 4990.0, [("stage", 5.01, 490.0), ("dispatch", 5.5, 700.0),
                                ("wait", 6.2, 3790.0)]))


@pytest.fixture()
def trace():
    return Trace(OPS, [], BRACKETS)


@pytest.fixture()
def table():
    return scopes.instruction_op_names(HLO)


def test_instructions_get_their_own_or_a_neighbours_op_name(table):
    assert table["fusion.1"] == FWD + "/layer_0/attn/q_proj/dot_general"
    # no op_name of its own: what most of its fused computation carries
    assert table["fusion.4"] == BWD + "/layer_1/attn/rope/mul"
    # nor here: the producer's, through the async start
    assert table["copy-done.8"] == FWD + "/lm_head/dot_general"
    # a prefetch of a parameter has no named producer
    assert table["slice-done.12"] is None and table["slice-start.12"] is None


def test_parts_and_unattributed_add_up_to_busy_time(trace, table):
    got = scopes.seconds_by_part(trace, table, scopes.load_rules())
    want = {}
    for _, s, d, part in TIMELINE:
        if part is not None:
            want[part] = want.get(part, 0.0) + d
    assert got == pytest.approx(want)
    assert sum(got.values()) == pytest.approx(BUSY_S)
    assert BUSY_S == pytest.approx(trace_reduce.busy_s(trace))


def test_first_match_wins_in_the_files_order():
    rules = scopes.load_rules()
    assert [p for p, _ in rules] == list(PARTS[:-1])
    # under attn AND a projection: the projection, listed first
    assert scopes.part_of("fusion.1 " + FWD + "/layer_3/attn/k_proj/add", rules) == "attn_proj"
    # the kernel is its instruction; its op_name on a copy is attention glue
    kernel = BWD + "/layer_3/attn/flash_bwd_dkv/pallas_call"
    assert scopes.part_of("flash_bwd_dkv.7 " + kernel, rules) == "flash_dkv"
    assert scopes.part_of("flash_bwd_dq.7 " + kernel.replace("dkv", "dq"), rules) == "flash_dq"
    assert scopes.part_of("copy.7 " + kernel, rules) == "attn_glue"
    # rope tables at the model's top level, rope inside attn: both glue
    assert scopes.part_of("fusion.2 " + FWD + "/rope/cos", rules) == "attn_glue"
    assert scopes.part_of("fusion.2 " + FWD + "/layer_0/attn/rope/mul", rules) == "attn_glue"
    assert scopes.part_of("fusion.2 " + FWD + "/embed/gather", rules) == "head_loss"
    assert scopes.part_of("fusion.2 " + R + "/jvp(loss)/reduce_sum", rules) == "head_loss"
    assert scopes.part_of("fusion.2 jit(fed_round)/fedavg/div", rules) == "round_glue"
    assert scopes.part_of("copy.2 jit(fed_round)/while/body/dynamic_slice", rules) == "round_glue"
    reversed_rules = list(reversed(rules))
    assert scopes.part_of("fusion.1 " + FWD + "/layer_3/attn/k_proj/add",
                          reversed_rules) == "attn_glue"
    assert scopes.part_of(None, rules) == "unattributed"
    assert scopes.part_of("fusion.9 something/else", rules) == "unattributed"


@pytest.mark.parametrize("op_name", [
    FWD + "/layer_0/mlp/up_proj/dot_general",
    BWD + "/layer_0/mlp/up_proj/dot_general",
    BWD + "/rematted_computation/layer_0/mlp/up_proj/dot_general",
    BWD + "/checkpoint/layer_0/mlp/gate_proj/add_any"])
def test_backward_and_rematted_ops_land_in_their_forward_part(op_name):
    assert scopes.part_of("fusion.5 " + op_name, scopes.load_rules()) == "mlp"


def test_join_by_name_and_read_from_the_event_name_agree(table):
    for key, event in EVENTS.items():
        joined = scopes.label_of(event, table)
        if table[key] is None:
            assert joined is None
            continue
        carried = scopes.label_of(
            event + f', metadata={{op_name="{table[key]}"}}', {})
        assert joined == carried == f"{key} {table[key]}"


def test_clock_offset_is_recovered(trace):
    got = scopes.clock_offset(trace, RECORDS)
    # each run sits 5 ms inside its bracket at both ends: the middle is exact
    assert got == pytest.approx(OFFSET, abs=1e-6)
    spans = scopes.program_spans(trace, RECORDS)
    assert [s[0] for s in spans] == ["sample", "stage", "dispatch", "wait",
                                     "stage", "dispatch", "wait"]
    assert spans[2][1] == pytest.approx(0.51, abs=1e-6)
    assert spans[2][2] == pytest.approx(1.5, abs=1e-6)


@pytest.mark.parametrize("extra_ms,aligned", [(0.0, True), (10.3, True),
                                               (10.5, False)])
def test_a_run_that_sticks_out_of_its_bracket_is_not_aligned(
        trace, extra_ms, aligned):
    """A run 10.3 ms longer than its 10 ms of slack sticks out 0.15 ms at
    each end (inside 0.2 ms); 10.5 ms longer, 0.25 ms: no alignment, and
    every idle metric reads nothing."""
    records = [dict(r) for r in RECORDS]
    for r in records:
        if r["name"] == "round/3/run":
            r["duration_ms"] += extra_ms
    assert (scopes.clock_offset(trace, records) is not None) == aligned
    assert (scopes.idle_ms_by_phase(trace, records) is not None) == aligned


def test_idle_is_cut_by_the_program_spans(trace):
    idle = scopes.idle_ms_by_phase(trace, RECORDS)
    # device idle in [0, 10]: [0, 1], [4.75, 5], [6.125, 6.25], [6.875, 7],
    # [7.375, 7.5], [7.5625, 8], [8.5, 10] = 3.5625 s
    stage = 0.1 + 0.4                  # sample and stage of round 2 in [0, 1]
    dispatch = 0.49 + 0.075            # [0.51, 1]; [6.125, 6.2]
    wait = 0.24 + (0.05 + 0.125 + 0.125 + 0.4375 + 1.49)  # [4.75, 4.99]; ...
    unspanned = 0.01 + 0.01 + 0.01     # [0, 0.01], [4.99, 5], [9.99, 10]
    assert stage + dispatch + wait + unspanned == pytest.approx(3.5625)
    assert idle["stage"] == pytest.approx(1e3 * stage / 2, abs=1e-3)
    assert idle["dispatch"] == pytest.approx(1e3 * dispatch / 2, abs=1e-3)
    assert idle["wait"] == pytest.approx(1e3 * wait / 2, abs=1e-3)
    assert idle["unspanned"] == pytest.approx(1e3 * unspanned / 2, abs=1e-3)
    window_idle = trace.window_s - trace_reduce.busy_s(trace)
    assert sum(idle.values()) == pytest.approx(1e3 * window_idle / 2)
    # and that is what the accepted round_host_ms reads, less nothing here:
    # the two brackets touch
    host = trace_reduce.span_minus_busy(trace, "bench.round")
    assert sum(idle.values()) == pytest.approx(1e3 * sum(host) / 2)


def _cell_readers():
    cell = spec.Cell("yi-6b.round-short")
    return cell, {m["name"]: cell.metric_reader(m["name"])
                  for m in cell.per_layer()}


def test_the_readers_find_their_files_and_add_up(trace):
    from benchmarks.harness import reducers

    cell, readers = _cell_readers()
    ctx = {"trace": trace, "hlo_text": HLO, "span_records": RECORDS,
           "rounds": 2}
    got = {}
    for m in cell.per_layer():
        if m["name"].endswith("_time_pct") and m["name"] != "flash_time_pct":
            got[m["name"]] = reducers.read(m, ctx, readers[m["name"]])
    assert set(got) == {p + "_time_pct" for p in PARTS}
    # nothing matched flash_dq / flash_dkv here: left out, not a made-up 0
    assert got.pop("flash_dq_time_pct") is None
    assert got.pop("flash_dkv_time_pct") is None
    assert sum(got.values()) == pytest.approx(100.0)
    assert got["flash_fwd_time_pct"] == pytest.approx(100 * 1.0 / BUSY_S)
    assert got["unattributed_time_pct"] == pytest.approx(100 * 0.0625 / BUSY_S)
    idle = {n: readers[n](ctx) for n in readers if n.startswith("idle_")}
    assert set(idle) == {"idle_stage_ms", "idle_dispatch_ms", "idle_wait_ms",
                         "idle_unspanned_ms"}
    host = reducers.read(
        next(m for m in cell.per_layer() if m["name"] == "round_host_ms"),
        ctx)
    assert sum(idle.values()) == pytest.approx(host)


def test_a_program_without_names_or_spans_reads_nothing(trace):
    """The parent of the PR that added them: no scopes' kernels in the
    text, no ``round/`` records in the tracer — ``None``, not an error."""
    _, readers = _cell_readers()
    ctx = {"trace": trace, "hlo_text": "", "span_records": [], "rounds": 2}
    for name, reader in readers.items():
        if reader is not None:
            assert reader(ctx) is None, name
    empty = {"trace": Trace([], [], BRACKETS), "hlo_text": HLO,
             "span_records": RECORDS, "rounds": 2}
    for name, reader in readers.items():
        if reader is not None:
            assert reader(empty) is None, name
