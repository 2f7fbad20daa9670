#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one chip, no children. Set-up (import, ``fedml_tpu.init``,
data, constructor, the harness's weights, the first ``train_one_round``,
which compiles or loads ``llm/fused_round``) is timed as ``setup_s``; then
``train_one_round`` is called for ``--seconds``; then the program's state
is freed and the plain reference follows the first call. Earlier lines say
what the run saw; the LAST line of stdout is the result.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.harness import check, program, reducers, spec  # noqa: E402
from benchmarks.harness import trace_reduce  # noqa: E402

ROUND_PROGRAM = "llm/fused_round"


def say(**line) -> None:
    print(json.dumps(line), flush=True)


def find_chip(cell):
    """The device, or exit non-zero with no result line."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"benchmark: needs a TPU, JAX found {dev.platform!r}")
    if len(devices) < cell.chips:
        raise SystemExit(f"benchmark: {cell.name} needs {cell.chips} chips, "
                         f"JAX found {len(devices)}")
    return devices, spec.load_peaks(dev.device_kind)


def cache_everything() -> None:
    """Small programs go to the persistent cache too (set-up stays the
    same from the second run on); where it lives is the program's rule."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def program_counters() -> dict:
    from fedml_tpu.telemetry import get_registry
    from fedml_tpu.telemetry.profiling import get_catalog

    out = {}
    for rec in get_catalog().records():
        out[rec.name + ".calls"] = rec.calls
        out[rec.name + ".compiles"] = rec.compile_events + rec.n_signatures
        out[rec.name + ".n_signatures"] = rec.n_signatures
        out[rec.name + ".fallback_calls"] = rec.fallback_calls
    reg = get_registry()
    for kind in ("hits", "misses"):
        out["cache." + kind] = int(
            reg.counter(f"jax/compile_cache_{kind}").value)
    return out


def plan_bytes() -> dict:
    """The compile-time memory plan of the round's executable."""
    from fedml_tpu.telemetry.profiling import get_catalog

    mem = get_catalog().program(ROUND_PROGRAM).last_compiled.memory_analysis()
    return {"argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "alias_bytes": int(mem.alias_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes)}


def memory_peak(devices, plan: dict) -> dict:
    """Peak on the fullest chip: the allocator's, or the plan's where that
    is larger (the v5e allocator's peak leaves out a program's temporaries)."""
    allocator = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                    for d in devices)
    planned = (plan["argument_bytes"] + plan["output_bytes"]
               - plan["alias_bytes"] + plan["temp_bytes"])
    return {"memory_peak_bytes": max(allocator, planned),
            "memory_peak_source": "plan" if planned > allocator else "allocator",
            "allocator_peak_bytes": allocator, "plan_bytes": planned}


def reference_round(cell, seed: int, precision="float32", fault="") -> dict:
    from benchmarks.harness.reference import Reference

    ref = Reference(seed, cell.config, cell.traffic, precision, fault)
    out = ref.run_round(1)
    out.update(ref.state())
    # its jitted methods hold it in a cycle: without this a 6B reference
    # keeps the chip until some later collection
    del ref
    gc.collect()
    return out


def measure(cell, seed: int, seconds: float, trace: bool, devices, peaks,
            t0: float = T0, span_factory=None, trace_summary: str = "") -> dict:
    """Everything after the look for a chip: set-up, window, comparison."""
    import jax

    stamps = [("import", time.perf_counter())]
    api = program.build(seed, cell.config, cell.traffic)
    stamps.append(("build", time.perf_counter()))
    program.install_weights(api, seed, cell.config)
    stamps.append(("weights", time.perf_counter()))
    first = api.train_one_round(1)
    stamps.append(("first_round", time.perf_counter()))
    snap = program.snapshot(api)
    snap["loss"] = float(first["train_loss"])
    before = program_counters()
    plan = plan_bytes()
    setup_s = time.perf_counter() - t0
    setup_parts = {name: t - prev for (name, t), prev in zip(
        stamps, [t0] + [t for _, t in stamps])}

    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(trace_dir)
        span_factory = span_factory or jax.profiler.TraceAnnotation
        # a trace of whole rounds a few seconds long: a long one only costs
        # the run minutes of reading (1.5e6 events in 12 s of yi-6b)
        seconds = min(seconds, trace_reduce.trace_layout()["traced_window_s"])
    win = program.window(api, seconds, first_round=2, span=span_factory)
    if trace:
        jax.profiler.stop_trace()
    after = program_counters()
    memory = memory_peak(devices, plan)
    program.free(api)
    del api

    tokens = win["rounds"] * program.tokens_per_round(cell.traffic)
    failed = sum(not math.isfinite(x) for x in win["losses"])
    say(phase="program", plan=plan, memory=memory, counters=after,
        setup_s=setup_s, setup_parts=setup_parts, first_loss=snap["loss"],
        window_losses=win["losses"][:4],
        rounds=win["rounds"], wall_s=win["wall_s"])

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": memory["memory_peak_bytes"],
              "memory_peak_source": memory["memory_peak_source"]}
    result = {"attempted": win["rounds"], "failed": failed}
    if not trace:
        values = {"train_tokens_per_s": tokens / win["wall_s"],
                  "round_s": win["wall_s"] / win["rounds"],
                  "setup_s": setup_s}
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end()}
    else:
        t1 = time.perf_counter()
        if trace_summary:
            trace_reduce.summarize_xplane(trace_dir, trace_summary)
        tr = trace_reduce.load_xplane(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = {"trace": tr, "config": cell.config, "traffic": cell.traffic,
               "peaks": peaks, "rounds": win["rounds"], "tokens": tokens,
               "counters": {"before": before, "after": after}}
        metrics = {}
        for m in cell.per_layer():
            value = reducers.read(m, ctx, cell.metric_reader(m["name"]))
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        device["busy_s"] = trace_reduce.busy_s(tr)
        device["window_s"] = tr.window_s
        result["breakdown"] = {
            "device_ops": trace_reduce.top_ops(tr),
            "idle_gaps": trace_reduce.idle_gaps(tr, "fed_round")}
        say(phase="trace", ops=len(tr.ops), modules=len(tr.modules),
            spans=len(tr.spans), reduce_s=time.perf_counter() - t1)
    result["device"] = device

    t1 = time.perf_counter()
    want = reference_round(cell, seed)
    nums = check.numbers(snap, want)
    verdict = check.judge(nums, cell.limits)
    say(phase="reference", seconds=time.perf_counter() - t1,
        loss=want["loss"], gaps={k: v["gap"] for k, v in nums.items()},
        leaves=nums["change"]["leaves"], left_out=nums["change"]["left_out"])
    correct = verdict["correct"] and failed == 0
    ordered = {"correct": correct, **result, "compared": verdict["compared"]}
    return ordered


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-summary", default="",
                    help="also write the trace's planes, lines and longest "
                         "names to this file (for a look by hand)")
    cli = ap.parse_args(argv)

    cell = spec.Cell(cli.workload)
    devices, peaks = find_chip(cell)
    cache_everything()
    result = measure(cell, cli.seed, cli.seconds, bool(cli.trace),
                     devices, peaks, trace_summary=cli.trace_summary)
    compared = " ".join(
        f"{k}={v['value']:.6g}/limit={v['limit']:g}{'' if v['ok'] else '(!)'}"
        for k, v in result["compared"].items())
    print(f"compared: {compared} correct={result['correct']}",
          file=sys.stderr, flush=True)
    say(**result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
