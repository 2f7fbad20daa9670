"""The built-in readers of per-layer metrics, by the ``reducer`` name a
metric's file gives. Each takes ``(ctx, params)`` and returns a number, or
``None`` where it finds nothing to read — then the metric is left out of
the line (a share of a peak or of a roofline is never reported as 0).

``ctx``: ``trace`` (``trace_reduce.Trace``), ``config``, ``traffic``,
``peaks``, ``rounds`` (whole rounds in the traced window), ``tokens``
(trained in it), ``counters`` (``{"before": {...}, "after": {...}}``).
"""
from __future__ import annotations

from . import flops, trace_reduce


def device_idle_pct(ctx, params):
    trace = ctx["trace"]
    if not trace.ops:
        return None
    return 100.0 * (1.0 - trace_reduce.busy_s(trace) / trace.window_s)


def module_ms(ctx, params):
    runs = trace_reduce.module_executions(ctx["trace"], params["module_pattern"])
    if not runs:
        return None
    return 1e3 * sum(m[2] - m[1] for m in runs) / len(runs)


def span_minus_busy_ms(ctx, params):
    parts = trace_reduce.span_minus_busy(ctx["trace"], params["span"])
    if not parts:
        return None
    return 1e3 * sum(parts) / len(parts)


def counter_delta(ctx, params):
    before = ctx["counters"]["before"].get(params["counter"])
    after = ctx["counters"]["after"].get(params["counter"])
    if before is None or after is None:
        return None
    return float(after - before)


def mfu_pct(ctx, params):
    trace = ctx["trace"]
    if not trace.ops or not ctx["tokens"]:
        return None
    work = flops.model_flops(ctx["config"], ctx["tokens"],
                             int(ctx["traffic"]["seq_len"]))["total"]
    return 100.0 * work / trace.window_s / (
        ctx["peaks"]["bf16_flops_per_s"] * len(trace.devices))


def kernel_roofline_pct(ctx, params):
    spent = trace_reduce.kernel_s(ctx["trace"], params["kernel_pattern"])
    if spent <= 0 or not ctx["tokens"]:
        return None
    work = getattr(flops, params["work"])(
        ctx["config"], ctx["tokens"], int(ctx["traffic"]["seq_len"]))
    least = max(work["flops"] / ctx["peaks"]["bf16_flops_per_s"],
                work["bytes"] / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / len(ctx["trace"].devices) / spent


def kernel_time_pct(ctx, params):
    trace = ctx["trace"]
    spent = trace_reduce.kernel_s(trace, params["kernel_pattern"])
    busy = trace_reduce.busy_s(trace)
    if spent <= 0 or busy <= 0:
        return None
    return 100.0 * spent / busy


def read(metric: dict, ctx: dict, reader=None):
    """One metric's value by its own reader, else by its named reducer."""
    if reader is not None:
        return reader(ctx)
    return globals()[metric["reducer"]](ctx, metric)
