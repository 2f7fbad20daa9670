"""The fused LLM round named from inside (PR 27): host spans of
``FedLLMAPI`` in the process tracer, the same spans as profiler
annotations, the memory-only tracer's ring, and the scopes that name the
round's XLA program. CPU, tiny widths; a timing here is never a speed."""
import glob
import os

import jax
import numpy as np
import pytest

from fedml_tpu import telemetry
from fedml_tpu.telemetry import spans as spans_mod

ROUNDS = (1, 2)
CHILDREN = ("sample", "stage", "dispatch", "wait")


def _api(tmp_path, **train):
    import fedml_tpu
    from fedml_tpu.arguments import load_arguments_from_dict
    from fedml_tpu.data import load_federated
    from fedml_tpu.train.llm.run_fedllm import FedLLMAPI

    never = 1 << 30  # no eval, no last round: a round is its four phases
    args = fedml_tpu.init(load_arguments_from_dict({
        "common_args": {"training_type": "simulation", "random_seed": 0},
        "data_args": {"dataset": "synthetic_lm", "max_seq_length": 16,
                      "vocab_size": 32, "train_size": 64, "test_size": 16},
        "model_args": {"model": "llama", "model_size": "tiny",
                       "lora_rank": 4},
        "train_args": {"federated_optimizer": "FedAvg",
                       "client_num_in_total": 4, "client_num_per_round": 2,
                       "comm_round": never, "frequency_of_the_test": never,
                       "local_steps_per_round": 2, "per_device_batch_size": 4,
                       "learning_rate": 5e-3, "on_device_round": True,
                       **train},
        "tracking_args": {"log_file_dir": str(tmp_path)},
    }))
    return FedLLMAPI(args, None, load_federated(args), mesh=None)


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    """Two fused rounds under a real profiler capture, with a fresh
    memory-only process tracer and no compile cache (a cache hit books no
    ``backend_compile`` to the first dispatch)."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.profiler import ProfileData

    tmp = tmp_path_factory.mktemp("round_tracing")
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    telemetry.reset_tracer()
    telemetry.reset_catalog()
    try:
        api = _api(tmp / "logs")
        trace_dir = str(tmp / "trace")
        jax.profiler.start_trace(trace_dir)
        reports = [api.train_one_round(r) for r in ROUNDS]
        jax.profiler.stop_trace()
        records = telemetry.get_tracer().records()
        programs = {r.name: (r.compile_events, r.n_signatures)
                    for r in telemetry.get_catalog().records()}
        text = telemetry.get_catalog().program(
            "llm/fused_round").last_compiled.as_text()
        engine = api.client.engine
        feed = jax.ShapeDtypeStruct((2, 2, engine.batch_size, engine.seq_len),
                                    np.int32)
        lowered = api._fed_round.lower(
            engine.params, engine.opt_state, api.global_exchange, feed, feed,
            jax.ShapeDtypeStruct(feed.shape[:3], np.float32),
            jax.ShapeDtypeStruct(feed.shape[:1], np.float32),
        ).as_text(debug_info=True)
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
        telemetry.reset_tracer()
    (xplane,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
    host_events = {ev.name for plane in ProfileData.from_file(xplane).planes
                   if plane.name.startswith("/host:")
                   for line in plane.lines for ev in line.events}
    return {"api": api, "reports": reports, "records": records,
            "programs": programs,
            "text": text, "lowered": lowered, "host_events": host_events,
            "logs": tmp / "logs"}


def _round(records, n):
    mine = [r for r in records if r["name"].startswith(f"round/{n}/")]
    (run,) = [r for r in mine if r["name"] == f"round/{n}/run"]
    kids = {r["name"].rsplit("/", 1)[1]: r for r in mine if r is not run}
    return run, kids


@pytest.mark.parametrize("n", ROUNDS)
def test_a_round_is_one_trace_of_four_phases(traced_run, n):
    run, kids = _round(traced_run["records"], n)
    assert tuple(kids) == CHILDREN  # in the order they ran, nothing else
    assert run["parent_id"] is None
    for kid in kids.values():
        assert kid["parent_id"] == run["span_id"]
        assert kid["trace_id"] == run["trace_id"]
        assert run["started"] <= kid["started"] and kid["ended"] <= run["ended"]
    tiled = sum(k["duration_ms"] for k in kids.values())
    assert 0.95 * run["duration_ms"] <= tiled <= run["duration_ms"]
    report = traced_run["reports"][n - ROUNDS[0]]
    assert np.isfinite(report["train_loss"]) and report["round"] == n
    # round_sec stays what it was: dispatch + wait, on the wall clock
    assert report["round_sec"] * 1e3 == pytest.approx(
        kids["dispatch"]["duration_ms"] + kids["wait"]["duration_ms"],
        rel=0.05, abs=1.0)


def test_spans_carry_the_counts_at_their_boundary(traced_run):
    api = traced_run["api"]
    engine = api.client.engine
    clients, steps = 2, 2
    rows = clients * steps * engine.batch_size
    tokens = rows * engine.seq_len
    run, kids = _round(traced_run["records"], ROUNDS[0])
    assert run["attrs"] == {"clients": clients, "steps": steps,
                            "tokens": tokens}
    assert kids["sample"]["attrs"] == {"clients": clients}
    # xs + ys int32, ms float32 per row, one float32 weight per client
    assert kids["stage"]["attrs"] == {
        "rows": rows, "tokens": tokens,
        "bytes": 2 * 4 * tokens + 4 * rows + 4 * clients}
    assert kids["dispatch"]["attrs"] == {"program": "llm/fused_round"}
    assert "attrs" not in kids["wait"]


def test_compile_lands_on_the_first_dispatch_only(traced_run):
    first = _round(traced_run["records"], ROUNDS[0])[1]
    second = _round(traced_run["records"], ROUNDS[1])[1]
    assert first["dispatch"]["compile_ms"] > 0
    assert "compile_ms" not in second["dispatch"]
    for rnd in (first, second):
        for name in ("sample", "stage", "wait"):
            assert "compile_ms" not in rnd[name]


STAGES = ["program/trace", "program/lower", "program/compile"]


def _stages_under(records, parent):
    return [(r["name"], r["attrs"]["program"]) for r in records
            if r["name"].startswith("program/")
            and r["parent_id"] == parent["span_id"]]


def test_the_constructor_is_llm_build_over_the_init_program(traced_run):
    records = traced_run["records"]
    (build,) = [r for r in records if r["name"] == "llm/build"]
    assert build["parent_id"] is None
    assert _stages_under(records, build) == [
        (s, "llm/init_params") for s in STAGES]
    assert build["trace_ms"] + build["lower_ms"] + build.get(
        "compile_ms", 0.0) <= build["duration_ms"]
    assert records.index(build) < min(
        i for i, r in enumerate(records) if r["name"] == "round/1/run")


def test_the_round_program_stages_under_the_first_dispatch_only(traced_run):
    records = traced_run["records"]
    first = _round(records, ROUNDS[0])[1]["dispatch"]
    second = _round(records, ROUNDS[1])[1]["dispatch"]
    assert _stages_under(records, first) == [
        (s, "llm/fused_round") for s in STAGES]
    assert _stages_under(records, second) == []
    (compiled,) = [r for r in records if r["name"] == "program/compile"
                   and r["parent_id"] == first["span_id"]]
    assert compiled["attrs"]["cache"] == "off"  # the fixture asks no cache
    # execute_ms is the dispatch less tracing, lowering and compile-or-load
    assert first["execute_ms"] == pytest.approx(
        first["duration_ms"] - first["trace_ms"] - first["lower_ms"]
        - first["compile_ms"])
    assert first["execute_ms"] < first["duration_ms"] - first["trace_ms"]


def test_one_listener_books_each_program_one_compile(traced_run):
    """``(compile_events, n_signatures)`` after two rounds: the round
    program's read (1, 1) before the listeners were folded into one (PR
    38's tree, the same rounds) and still do; the init program's compile,
    uncataloged before, is its own now."""
    assert traced_run["programs"]["llm/fused_round"] == (1, 1)
    assert traced_run["programs"]["llm/init_params"] == (1, 1)


def test_fedllm_opens_no_second_tracer(traced_run):
    assert not hasattr(traced_run["api"], "event")
    spans_mod._flush_live_tracers()  # what interpreter exit would land
    assert not glob.glob(str(traced_run["logs"] / "**" / "events.jsonl"),
                         recursive=True)


@pytest.mark.parametrize("phase", ("run",) + CHILDREN)
def test_spans_reach_a_profiler_capture(traced_run, phase):
    """Every ``Tracer.span`` is a ``TraceAnnotation`` too: the names sit on
    a host line of the capture that was running."""
    for n in ROUNDS:
        assert f"round/{n}/{phase}" in traced_run["host_events"]


def test_host_path_leaves_sample_clients_aggregate(tmp_path):
    telemetry.reset_tracer()
    try:
        api = _api(tmp_path, on_device_round=False, epochs=1)
        assert not api.on_device
        api.train_one_round(3)
        names = [r["name"] for r in telemetry.get_tracer().records()
                 if r["name"].startswith("round/3/")]
    finally:
        telemetry.reset_tracer()
    assert names[0] == "round/3/sample" and names[-1] == "round/3/aggregate"
    trains = names[1:-1]
    assert len(trains) == 2 and all(
        n.startswith("round/3/client/") and n.endswith("/train")
        for n in trains)


def test_eval_and_checkpoint_spans_only_when_they_run(tmp_path):
    telemetry.reset_tracer()
    try:
        api = _api(tmp_path / "logs", frequency_of_the_test=2,
                   checkpoint_dir=str(tmp_path / "ckpt"), save_every_rounds=2)
        api.train_one_round(1)
        api.train_one_round(2)
        names = [r["name"] for r in telemetry.get_tracer().records()]
    finally:
        telemetry.reset_tracer()
    assert "round/2/eval" in names and "round/2/checkpoint" in names
    assert "round/1/eval" not in names and "round/1/checkpoint" not in names
    run = names.index("round/2/run")  # a parent ends after its children
    assert names.index("round/2/eval") < names.index("round/2/checkpoint") < run


def test_memory_only_tracer_is_a_ring_of_its_newest():
    tracer = spans_mod.Tracer()
    extra = 40
    for i in range(spans_mod.RING_RECORDS + extra):
        with tracer.span(f"round/{i}/run"):
            pass
    records = tracer.records()
    assert len(records) == spans_mod.RING_RECORDS
    assert records[0]["name"] == f"round/{extra}/run"
    assert records[-1]["name"] == f"round/{spans_mod.RING_RECORDS + extra - 1}/run"
    assert tracer.flush() is None and len(tracer.records()) == len(records)


def test_tracer_with_a_sink_still_lands_every_record(tmp_path):
    tracer = spans_mod.Tracer(sink_dir=str(tmp_path), buffer_limit=8)
    for i in range(20):
        with tracer.span(f"round/{i}/run"):
            pass
    tracer.flush()
    assert tracer.records() == []
    with open(tmp_path / "spans.jsonl") as f:
        assert len(f.readlines()) == 20


def test_span_name_lint_passes():
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "check_span_names.py")],
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stdout + out.stderr


# (scope, also in the backward pass). The optimizer and FedAvg are not
# differentiated; the frozen embedding has no gradient; the head's two
# products are both made in the forward rule of the loss's own VJP
# (models/llm/head_loss.py), whose backward rule only multiplies by the
# cotangent, under ``loss``. ``client_switch``
# is in the source too but lowers to no operation: merge_lora swaps tree
# leaves, and what the device runs for it are the scan's own carry copies.
SCOPES = [("optimizer", False), ("fedavg", False), ("embed", False),
          ("loss", True), ("lm_head", False), ("rope", True),
          ("attn_layout", True)]


def _op_names(text):
    import re

    return re.findall(r'op_name="([^"]*)"', text)


@pytest.mark.parametrize("scope,backward", SCOPES, ids=[s for s, _ in SCOPES])
def test_round_program_names_what_no_module_names(traced_run, scope, backward):
    import re

    # a scope is a path segment, bare or wrapped by a transformation:
    # .../rope/mul, .../jvp(loss)/..., .../transpose(jvp(loss))/...
    seg = re.compile(r"(?:^|[/(])" + scope + r"(?:[/)]|$)")
    # what the compiler kept (an optimizer may fold a backward transpose
    # into its matmul): every scope still names some instruction
    assert any(seg.search(n) for n in _op_names(traced_run["text"])), scope
    # what the program says, before any optimization: forward, and backward
    # where the scope is differentiated
    said = [n for n in re.findall(r'"([^"\n]*)"', traced_run["lowered"])
            if seg.search(n)]
    assert any("transpose(" not in n for n in said)
    assert any("transpose(" in n for n in said) == backward


def test_flax_scopes_and_the_missing_client_switch(traced_run):
    names = _op_names(traced_run["text"])
    for part in ("layer_0/attn/q_proj", "layer_1/mlp/gate_proj",
                 "layer_0/input_norm", "final_norm"):
        assert any(part in n for n in names), part
    assert not any("client_switch" in n for n in names)
