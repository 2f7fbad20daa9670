"""The five ``setup_*`` readers on synthetic span records: what set-up is
(everything up to the end of the first ``round/<n>/run``), the self time
of ``llm/build``, the count of compiles that missed the cache, and
nothing at all where the program leaves no ``llm/build``."""
import itertools

import pytest

from benchmarks.harness import spec

READERS = ("setup_trace_s", "setup_lower_s", "setup_compile_s",
           "setup_build_s", "setup_cache_misses")
_ids = itertools.count()


def _reader(name):
    return spec.Cell("yi-6b.round-short").metric_reader(name)


def _span(name, ms, parent=None, **attrs):
    rec = {"name": name, "span_id": f"s{next(_ids)}",
           "parent_id": parent["span_id"] if parent else None,
           "duration_ms": float(ms)}
    if attrs:
        rec["attrs"] = attrs
    return rec


def _stages(parent, program, trace, lower, compile_, cache="hit"):
    return [_span("program/trace", trace, parent, program=program),
            _span("program/lower", lower, parent, program=program),
            _span("program/compile", compile_, parent, program=program,
                  cache=cache)]


def _setup_then_window():
    """In the order a ring holds them (by end): the constructor's init
    program, the first round's round program, then a later round that
    compiles a new signature — which set-up must not count."""
    build = _span("llm/build", 9000.0)
    first = _span("round/1/dispatch", 52000.0)
    later = _span("round/7/dispatch", 3000.0)
    return [
        {"name": "loss/plan", "point": True, "attrs": {"rows": 1}},
        *_stages(build, "llm/init_params", 400.0, 600.0, 2500.0, cache="miss"),
        build,
        _span("round/1/sample", 1.0),
        *_stages(first, "llm/fused_round", 38000.0, 3400.0, 5500.0),
        first,
        _span("round/1/run", 52100.0),
        _span("round/2/run", 900.0),
        *_stages(later, "llm/fused_round", 30000.0, 3000.0, 4000.0,
                 cache="miss"),
        later,
        _span("round/7/run", 37500.0),
    ]


@pytest.mark.parametrize("name,want", [
    ("setup_trace_s", 38.4), ("setup_lower_s", 4.0),
    ("setup_compile_s", 8.0), ("setup_build_s", 5.5),
    ("setup_cache_misses", 1.0)])
def test_set_up_ends_with_the_first_round(name, want):
    assert _reader(name)({"span_records": _setup_then_window()}) == \
        pytest.approx(want)


def test_build_self_time_takes_out_every_staged_descendant():
    build = _span("llm/build", 1000.0)
    engine = _span("llm/engine", 500.0, build)  # a span between them
    records = [*_stages(engine, "llm/init_params", 100.0, 50.0, 200.0),
               engine,
               *_stages(build, "llm/other", 10.0, 20.0, 30.0),
               build,
               _span("program/trace", 70.0, program="llm/fused_round"),
               _span("round/1/run", 80.0)]
    ctx = {"span_records": records}
    assert _reader("setup_build_s")(ctx) == pytest.approx(0.59)
    # the stage outside llm/build still counts as set-up's tracing
    assert _reader("setup_trace_s")(ctx) == pytest.approx(0.18)


@pytest.mark.parametrize("name", READERS)
def test_nothing_without_llm_build(name):
    records = [r for r in _setup_then_window() if r["name"] != "llm/build"]
    assert _reader(name)({"span_records": records}) is None
    # and nothing before the first round has ended
    cut = [r for r in _setup_then_window() if not r["name"].endswith("/run")]
    assert _reader(name)({"span_records": cut}) is None


def test_no_miss_on_a_warm_set_up():
    records = [r for r in _setup_then_window()
               if r.get("attrs", {}).get("cache") != "miss"]
    assert _reader("setup_cache_misses")({"span_records": records}) == 0.0


def test_read_from_the_process_tracer_and_never_from_a_full_ring():
    from fedml_tpu import telemetry
    from fedml_tpu.telemetry import spans

    telemetry.reset_tracer()
    try:
        tracer = telemetry.get_tracer()
        with tracer.span("llm/build"):
            with tracer.span("program/trace", program="llm/init_params"):
                pass
        with tracer.span("round/1/run"):
            pass
        assert _reader("setup_trace_s")({}) >= 0.0
        assert _reader("setup_cache_misses")({}) == 0.0
        for _ in range(spans.RING_RECORDS):
            tracer.event("filler")
        assert _reader("setup_trace_s")({}) is None
    finally:
        telemetry.reset_tracer()
