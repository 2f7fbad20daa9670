"""Set-up from inside the program (ISSUE 39): a cataloged program's first
call is three spans of the process tracer — ``program/trace``,
``program/lower``, ``program/compile`` (``cache`` = hit / miss / off) —
children of whatever span is open, and the process has ONE
``jax.monitoring`` listener that feeds the spans, the ``jax/compile_ms``
histogram, the catalog and the cache counters. CPU, tiny shapes."""
import jax
import jax.numpy as jnp
import pytest

from fedml_tpu import telemetry
from fedml_tpu.telemetry import spans as spans_mod
from fedml_tpu.telemetry.profiling import get_catalog, wrap_jit

STAGES = ["program/trace", "program/lower", "program/compile"]


@pytest.fixture
def tracer():
    telemetry.reset_tracer()
    yield telemetry.get_tracer()
    telemetry.reset_tracer()


def _stages(records, program):
    return [r for r in records if r["name"].startswith("program/")
            and r["attrs"]["program"] == program]


def _program(name, scale):
    return wrap_jit(name, jax.jit(lambda x: jnp.cos(x) * scale))


def test_first_call_is_three_stages_under_the_open_span(tracer):
    prog = _program("test/stages_first", 3.25)
    with tracer.span("round/1/dispatch") as outer:
        prog(jnp.ones((5,)))
    stages = _stages(tracer.records(), "test/stages_first")
    assert [r["name"] for r in stages] == STAGES  # in the order they ran
    for r in stages:
        assert r["parent_id"] == outer.span_id
        assert r["trace_id"] == outer.trace_id
        assert r["attrs"]["program"] == "test/stages_first"
    assert [r["started"] for r in stages] == sorted(r["started"]
                                                    for r in stages)
    # compile_wall_ms is the three spans' durations, nothing timed apart
    assert prog.record.compile_wall_ms == pytest.approx(
        sum(r["duration_ms"] for r in stages))


def test_a_known_signature_leaves_no_stage_and_a_new_one_three(tracer):
    prog = _program("test/stages_again", 4.5)
    prog(jnp.ones((6,)))
    prog(jnp.ones((6,)))  # fast path
    assert len(_stages(tracer.records(), "test/stages_again")) == 3
    assert prog.record.n_signatures == 1
    events = prog.record.compile_events
    assert events >= 1
    prog(jnp.ones((11,)))  # new signature: a variant of its own
    assert len(_stages(tracer.records(), "test/stages_again")) == 6
    assert prog.record.n_signatures == 2
    assert prog.record.compile_events == 2 * events
    prog(jnp.ones((11,)))
    prog(jnp.ones((6,)))  # back to the first variant through the slow path
    assert len(_stages(tracer.records(), "test/stages_again")) == 6
    assert prog.record.calls == 5


def test_a_span_with_stages_carries_their_times(tracer):
    prog = _program("test/stages_split", 5.75)
    with tracer.span("round/1/dispatch"):
        prog(jnp.ones((7,)))
    *stages, outer = [r for r in tracer.records()
                      if r["name"] in STAGES + ["round/1/dispatch"]]
    trace, lower, compiled = stages
    assert outer["trace_ms"] == pytest.approx(trace["duration_ms"])
    assert outer["lower_ms"] == pytest.approx(lower["duration_ms"])
    # compile_ms stays the listener's compile-or-load, rolled up from the
    # compile span (plus whatever else compiled while the outer span was
    # innermost); execute_ms is what is left of the outer span
    assert outer["compile_ms"] >= compiled["compile_ms"] > 0
    assert outer["execute_ms"] == pytest.approx(
        outer["duration_ms"] - outer["trace_ms"] - outer["lower_ms"]
        - outer["compile_ms"])
    assert "trace_ms" not in compiled


def test_one_listener_however_many_installers():
    from jax._src import monitoring

    telemetry.install_jax_compile_listener()
    telemetry.install_compile_cache_counters()
    telemetry.install_compile_cache_counters()
    spans_mod.Tracer()
    get_catalog()
    assert monitoring.get_event_duration_listeners().count(
        spans_mod._on_jax_duration) == 1
    assert monitoring.get_event_listeners().count(spans_mod._on_jax_event) == 1
    ours = [fn for fn in monitoring.get_event_duration_listeners()
            + monitoring.get_event_listeners()
            if getattr(fn, "__module__", "").startswith("fedml_tpu")]
    assert len(ours) == 2


# -- the persistent compilation cache, as the compile span sees it ---------
@pytest.fixture
def compile_cache():
    """A switch for JAX's persistent cache, restored afterwards: ``on(dir)``
    caches every program in ``dir``, ``off()`` asks no cache."""
    from jax.experimental.compilation_cache import compilation_cache

    keys = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}

    class Switch:
        @staticmethod
        def on(path):
            jax.config.update("jax_enable_compilation_cache", True)
            jax.config.update("jax_compilation_cache_dir", str(path))
            jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
            jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
            compilation_cache.reset_cache()

        @staticmethod
        def off():
            jax.config.update("jax_enable_compilation_cache", False)
            compilation_cache.reset_cache()

    try:
        yield Switch
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


def _cache_of(tracer, program):
    (compiled,) = [r for r in _stages(tracer.records(), program)
                   if r["name"] == "program/compile"]
    return compiled["attrs"]["cache"]


def test_cache_reads_off_where_no_cache_is_asked(tracer, compile_cache):
    compile_cache.off()
    _program("test/stages_off", 8.25)(jnp.ones((5,)))
    assert _cache_of(tracer, "test/stages_off") == "off"


def test_cache_reads_miss_then_hit(tracer, compile_cache, tmp_path):
    compile_cache.on(tmp_path / "cache")
    reg = telemetry.get_registry()
    hits = reg.counter("jax/compile_cache_hits").value
    _program("test/stages_cached", 9.75)(jnp.ones((5,)))
    assert _cache_of(tracer, "test/stages_cached") == "miss"
    assert reg.counter("jax/compile_cache_hits").value == hits
    jax.clear_caches()
    telemetry.reset_tracer()
    again = telemetry.get_tracer()
    _program("test/stages_cached", 9.75)(jnp.ones((5,)))  # a fresh wrap
    assert _cache_of(again, "test/stages_cached") == "hit"
    assert reg.counter("jax/compile_cache_hits").value == hits + 1


def test_report_counts_each_stage_once(tmp_path):
    """``build_report``'s split follows the spans' rule: the stage spans'
    times are already on the span they ran under."""
    import json

    def span(name, duration, **extra):
        return {"name": name, "started": 1.0, "ended": 1.0 + duration / 1e3,
                "duration_ms": duration, **extra}

    spans = [span("program/trace", 30.0), span("program/lower", 10.0),
             span("program/compile", 25.0, compile_ms=20.0),
             span("round/1/dispatch", 100.0, trace_ms=30.0, lower_ms=10.0,
                  compile_ms=20.0, execute_ms=40.0),
             span("round/1/run", 150.0)]
    with open(tmp_path / "spans.jsonl", "w") as f:
        for rec in spans:
            f.write(json.dumps(rec) + "\n")
    report = telemetry.build_report(str(tmp_path))
    assert (report["trace_ms"], report["lower_ms"], report["compile_ms"]) \
        == (30.0, 10.0, 20.0)
    assert "trace 30.0 ms, lower 10.0 ms, compile 20.0 ms" in \
        telemetry.format_report(report)
