"""From a profiler trace to numbers: the benchmark's own reduction.

``Trace`` holds three plain lists on one clock, in seconds:
``ops`` and ``modules`` ``(name, start, end, device)`` from the device
planes, ``spans`` ``(name, start, end)`` of the harness's own
``TraceAnnotation``s from the host planes. ``load_xplane`` fills it from the
``.xplane.pb`` JAX writes (layout names in ``trace_layout.json``); every
reducer below takes a ``Trace`` and is checked on a hand-made one in
``benchmarks/tests``.
"""
from __future__ import annotations

import glob
import json
import os
import re


class Trace:
    def __init__(self, ops, modules, spans, window=None):
        self.ops, self.modules, self.spans = list(ops), list(modules), list(spans)
        if window is None:
            rounds = [s for s in self.spans if s[0] == "bench.round"]
            window = (min(s[1] for s in rounds), max(s[2] for s in rounds))
        self.window = window
        self.devices = sorted({o[3] for o in self.ops}) or [0]

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def trace_layout() -> dict:
    with open(os.path.join(os.path.dirname(__file__),
                           "trace_layout.json")) as f:
        return json.load(f)


def load_xplane(trace_dir: str) -> Trace:
    from jax.profiler import ProfileData

    layout = trace_layout()
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise RuntimeError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    ops, modules, spans = [], [], []
    container = re.compile(layout["container_ops"])
    for plane in data.planes:
        if plane.name.startswith(layout["device_plane_prefix"]):
            dev = int(plane.name[len(layout["device_plane_prefix"]):].split()[0])
            for line in plane.lines:
                into = {layout["ops_line"]: ops,
                        layout["modules_line"]: modules}.get(line.name)
                if into is None:
                    continue
                for ev in line.events:
                    # a while loop's own event spans everything inside it:
                    # only the ops that do the work count as busy
                    if into is ops and container.search(ev.name):
                        continue
                    start = ev.start_ns * 1e-9
                    into.append((ev.name, start,
                                 start + ev.duration_ns * 1e-9, dev))
        elif plane.name.startswith(layout["host_plane_prefix"]):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(layout["span_prefix"]):
                        start = ev.start_ns * 1e-9
                        spans.append((ev.name, start,
                                      start + ev.duration_ns * 1e-9))
    return Trace(ops, modules, spans)


def summarize_xplane(trace_dir: str, out_path: str, top: int = 40) -> None:
    """Planes, lines and the names that took most time: for a look by hand
    before a pattern is written against the trace."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
    with open(out_path, "w") as f:
        f.write(f"file bytes {os.path.getsize(paths[0])}\n")
        for plane in data.planes:
            f.write(f"PLANE {plane.name!r}\n")
            for line in plane.lines:
                total, count, first = {}, 0, None
                for ev in line.events:
                    count += 1
                    first = ev.start_ns if first is None else first
                    total[ev.name] = total.get(ev.name, 0) + ev.duration_ns
                f.write(f"  LINE {line.name!r} events {count} first_ns {first}\n")
                for name, ns in sorted(total.items(), key=lambda kv: -kv[1])[:top]:
                    f.write(f"    {ns * 1e-6:12.3f} ms  {name[:160]}\n")


def union(intervals) -> list:
    """Merged ``(start, end)`` intervals, sorted."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def busy_intervals(trace: Trace, device) -> list:
    lo, hi = trace.window
    return union(_clip([(o[1], o[2]) for o in trace.ops if o[3] == device],
                       lo, hi))


def busy_s(trace: Trace) -> float:
    """Seconds an operation ran on the device, averaged over the devices."""
    per = [sum(e - s for s, e in busy_intervals(trace, d))
           for d in trace.devices]
    return sum(per) / len(per)


def _inside(trace: Trace, ops) -> float:
    lo, hi = trace.window
    return sum(e - s for s, e in _clip([(o[1], o[2]) for o in ops], lo, hi))


def matching_ops(trace: Trace, pattern: str) -> list:
    rx = re.compile(pattern)
    return [o for o in trace.ops if rx.search(o[0])]


def kernel_s(trace: Trace, pattern: str) -> float:
    """Summed device time of the ops whose name matches, per device."""
    return _inside(trace, matching_ops(trace, pattern)) / len(trace.devices)


def module_executions(trace: Trace, pattern: str) -> list:
    rx = re.compile(pattern)
    lo, hi = trace.window
    return [m for m in trace.modules
            if rx.search(m[0]) and m[1] >= lo and m[2] <= hi]


def span_minus_busy(trace: Trace, span_name: str) -> list:
    """For each span of that name: its length less the device-busy time
    inside it (device 0's) — the host's own share of the call."""
    busy = busy_intervals(trace, trace.devices[0])
    out = []
    for name, s, e in trace.spans:
        if name == span_name:
            inside = sum(b - a for a, b in _clip(busy, s, e))
            out.append((e - s) - inside)
    return out


def short_name(name: str, width: int = 120) -> str:
    """An op's trace name is its whole HLO line: keep the instruction's
    name with its number folded (one op of 32 layers is one entry), its
    opcode, a custom call's target and its result type without layouts."""
    m = re.match(r"(%?[\w.\-]+) = (.*?) ([\w\-]+)\(", name)
    if not m:
        return " ".join(re.sub(r"\d+", "N", name).split())[:width]
    target = re.search(r'custom_call_target="([^"]+)"', name)
    parts = [re.sub(r"\d+", "N", m.group(1)), m.group(3),
             target.group(1) if target else "",
             re.sub(r"\{[^}]*\}", "", m.group(2))]
    return " ".join(" ".join(parts).split())[:width]


def top_ops(trace: Trace, n: int = 10) -> list:
    """``[name, seconds]`` of the ops that took most device time."""
    lo, hi = trace.window
    by_name = {}  # every round repeats the same names: shorten each once
    for name, s, e, _ in trace.ops:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            by_name[name] = by_name.get(name, 0.0) + (e - s)
    total = {}
    for name, seconds in by_name.items():
        key = short_name(name)
        total[key] = total.get(key, 0.0) + seconds
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / len(trace.devices)] for k, v in ranked]


def idle_gaps(trace: Trace, module_pattern: str, n: int = 10) -> list:
    """``[where, seconds]``: idle time of device 0 inside the window, summed
    by where each gap falls against the harness's spans and the round's
    module: ``round.before_program`` (inside a round span, before the
    module starts), ``round.after_program`` (after it ends),
    ``round.inside_program`` (between ops of the module) and
    ``between_rounds`` (outside every round span)."""
    lo, hi = trace.window
    busy = busy_intervals(trace, trace.devices[0])
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    rounds = sorted((s, e) for name, s, e in trace.spans
                    if name == "bench.round")
    mods = sorted((m[1], m[2]) for m in module_executions(trace, module_pattern))
    cuts = sorted({t for iv in rounds + mods for t in iv})
    total = {}
    for a, b in gaps:
        pieces = [a] + [t for t in cuts if a < t < b] + [b]
        for lo_, hi_ in zip(pieces, pieces[1:]):
            mid = (lo_ + hi_) / 2
            where = "between_rounds"
            for s, e in rounds:
                if s <= mid <= e:
                    inside = [m for m in mods if s <= m[0] <= e]
                    if not inside or mid < inside[0][0]:
                        where = "round.before_program"
                    elif mid > inside[-1][1]:
                        where = "round.after_program"
                    else:
                        where = "round.inside_program"
                    break
            total[where] = total.get(where, 0.0) + (hi_ - lo_)
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])][:n]
