"""Join a device trace to the names the round's program gives itself.

Two joins, both for the per-layer metrics that read the program from inside
(``metrics/scopes.json`` and the ``*_time_pct`` / ``idle_*_ms`` readers):

*Device time by part of the program.* A trace's op event is named by its HLO
instruction line without metadata, so each op is joined on the ``%name`` its
event name starts with to the ``op_name`` that instruction carries in the
round's executable (``llm/fused_round``'s ``last_compiled.as_text()``), and
``"<instruction name> <op_name>"`` is matched against an ordered list of
``[part, regex]`` kept as data; the first match wins.

*Device idle by what the host was doing.* The program's own spans
(``round/<n>/run`` and its children, in the process tracer's memory) are put
on the trace's clock by the one pair both sides have: each ``bench.round``
span of the trace brackets exactly one ``round/<n>/run``.

Everything takes plain values (a ``Trace``, HLO text, span records) and is
checked on hand-made ones in ``benchmarks/tests/test_scopes.py``; only
``from_ctx`` reaches into the running process.
"""
from __future__ import annotations

import bisect
import collections
import json
import os
import re

from . import trace_reduce

ROUND_PROGRAM = "llm/fused_round"
RULES_FILE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "metrics", "scopes.json")
UNATTRIBUTED = "unattributed"
# how far a program span may stick out of the harness's span around it
# before the two clocks count as not aligned
ALIGN_TOLERANCE_S = 0.2e-3
IDLE_LABELS = {"sample": "stage", "stage": "stage", "dispatch": "dispatch",
               "wait": "wait"}

_INSTRUCTION = re.compile(r"\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_COMPUTATION = re.compile(r"(?:ENTRY\s+)?%?([\w.\-]+) \(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_FIRST_OPERAND = re.compile(r" [\w\-]+\([^%)]*%([\w.\-]+)")
_PRODUCER_HOPS = 6


def load_rules(path: str = RULES_FILE) -> list:
    """``[(part, compiled regex)]`` in the file's order."""
    with open(path) as f:
        return [(part, re.compile(rx)) for part, rx in json.load(f)["parts"]]


def part_of(label, rules) -> str:
    """The part of an op labelled ``"<instruction name> <op_name>"``."""
    if label:
        for part, rx in rules:
            if rx.search(label):
                return part
    return UNATTRIBUTED


def instruction_op_names(hlo_text: str) -> dict:
    """``instruction name -> op_name`` for a compiled module's text.

    An instruction's ``op_name`` is its own. A fusion the compiler left
    without one (a multi-output fusion: its root is a bare tuple) takes the
    ``op_name`` most instructions of its fused computation carry; any other
    instruction without one (a layout copy, a reshape, an async copy's
    done) takes its nearest producer's along its first operand. What is
    still unnamed after that maps to ``None``."""
    own, calls, first, members = {}, {}, {}, collections.defaultdict(list)
    computation = None
    for line in hlo_text.splitlines():
        if not line.startswith(" "):
            m = _COMPUTATION.match(line)
            computation = m.group(1) if m else None
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name = m.group(1)
        found = _OP_NAME.search(line)
        own[name] = found.group(1) if found else None
        if own[name]:
            members[computation].append(own[name])
        found = _CALLS.search(line)
        calls[name] = found.group(1) if found else None
        found = _FIRST_OPERAND.search(line, m.end())
        first[name] = found.group(1) if found else None

    def named(name):
        if own[name]:
            return own[name]
        inside = members.get(calls[name])
        if inside:
            return collections.Counter(inside).most_common(1)[0][0]
        return None

    out = {}
    for name in own:
        at, hops = name, 0
        while at in own and hops <= _PRODUCER_HOPS:
            out[name] = named(at)
            if out[name]:
                break
            at, hops = first[at], hops + 1
    return out


def label_of(event_name: str, table: dict):
    """``"<instruction name> <op_name>"`` of an op event, or ``None`` where
    it has no ``op_name``: read from the event's own name where that carries
    one, else joined on the ``%name`` the event name starts with. The
    instruction's name is kept because a kernel shares its ``op_name`` with
    the layout copies the compiler puts in front of it."""
    m = _INSTRUCTION.match(event_name)
    instruction = m.group(1) if m else event_name.lstrip("%")
    found = _OP_NAME.search(event_name)
    op_name = found.group(1) if found else table.get(instruction)
    return f"{instruction} {op_name}" if op_name else None


def seconds_by_part(trace, table: dict, rules: list) -> dict:
    """``part -> device seconds`` inside the window, averaged over the
    devices; ops no rule matched are under ``UNATTRIBUTED``."""
    lo, hi = trace.window
    by_name = collections.defaultdict(float)
    for name, s, e, _ in trace.ops:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            by_name[name] += e - s
    out = collections.defaultdict(float)
    for name, seconds in by_name.items():
        part = part_of(label_of(name, table), rules)
        out[part] += seconds / len(trace.devices)
    return dict(out)


def _phase(record: dict):
    """``<phase>`` of a completed ``round/<n>[/client/<id>]/<phase>`` span
    record; ``None`` for any other record (a point event has no duration)."""
    name = record.get("name", "")
    if name.startswith("round/") and "duration_ms" in record:
        return name.rsplit("/", 1)[1]
    return None


def clock_offset(trace, records: list):
    """Seconds to add to a record's ``started`` to land on the trace's
    clock, or ``None`` where the two cannot be aligned.

    The window's ``bench.round`` spans, in order, bracket the newest
    ``round/<n>/run`` records, in order: a run starts after its bracket
    does and ends before it, so the offset lies between the largest
    ``bracket.start - run.started`` and the smallest ``bracket.end -
    run.ended``. The middle of that interval is returned; an interval
    emptier than ``ALIGN_TOLERANCE_S`` (some run would stick out of its
    bracket by more than that) means the clocks do not line up."""
    brackets = sorted((s, e) for name, s, e in trace.spans
                      if name == "bench.round")
    runs = sorted((r for r in records if _phase(r) == "run"),
                  key=lambda r: r["started"])[-len(brackets):]
    if not brackets or len(runs) != len(brackets):
        return None
    least = max(b[0] - r["started"] for b, r in zip(brackets, runs))
    most = min(b[1] - (r["started"] + r["duration_ms"] * 1e-3)
               for b, r in zip(brackets, runs))
    if least - most > 2 * ALIGN_TOLERANCE_S:
        return None
    return (least + most) / 2


def program_spans(trace, records: list):
    """``[(phase, start, end)]`` on the trace's clock: the children of the
    window's ``round/<n>/run`` spans, or ``None`` where there are none or
    the clocks do not line up."""
    offset = clock_offset(trace, records)
    if offset is None:
        return None
    lo, hi = trace.window
    out = []
    for r in records:
        phase = _phase(r)
        if phase is None or phase == "run":
            continue
        start = r["started"] + offset
        end = start + r["duration_ms"] * 1e-3
        if end > lo and start < hi:
            out.append((phase, start, end))
    return sorted(out, key=lambda s: s[1]) or None


def idle_ms_by_phase(trace, records: list, busy: list = None):
    """``label -> mean ms a round`` of device 0's idle time inside the
    window, by the program span it falls under: ``stage`` (``sample`` or
    ``stage``), ``dispatch``, ``wait``, and ``unspanned`` for the rest.
    ``None`` without ops, rounds or aligned spans. ``busy``: device 0's
    ``busy_intervals`` where the caller has them already."""
    spans = program_spans(trace, records)
    rounds = sum(1 for s in trace.spans if s[0] == "bench.round")
    if not spans or not trace.ops or not rounds:
        return None
    lo, hi = trace.window
    if busy is None:
        busy = trace_reduce.busy_intervals(trace, trace.devices[0])
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    total = {"stage": 0.0, "dispatch": 0.0, "wait": 0.0,
             "unspanned": sum(b - a for a, b in gaps)}
    ends = [b for _, b in gaps]
    for phase, start, end in spans:
        label = IDLE_LABELS.get(phase)
        if label is None:
            continue
        under = 0.0
        # the gaps are in order and disjoint: from the first that ends
        # after the span starts to the last that starts before it ends
        for a, b in gaps[bisect.bisect_right(ends, start):]:
            if a >= end:
                break
            under += min(b, end) - max(a, start)
        total[label] += under
        total["unspanned"] -= under
    return {k: 1e3 * v / rounds for k, v in total.items()}


def from_ctx(ctx: dict) -> dict:
    """What the readers share, worked out once a run and kept in ``ctx``:
    ``parts`` (``seconds_by_part`` or ``None``), ``idle``
    (``idle_ms_by_phase`` or ``None``) and ``busy_s``. The HLO text and the
    span records come from ``ctx`` where a test put them there, else from
    the running program: the catalog's ``llm/fused_round`` executable and
    the process tracer's memory. A program without either (the parent of
    the PR that added them) gives ``None``s, never an error."""
    if "scopes" in ctx:
        return ctx["scopes"]
    trace = ctx["trace"]
    text, records = ctx.get("hlo_text"), ctx.get("span_records")
    if text is None:
        from fedml_tpu.telemetry.profiling import get_catalog

        program = get_catalog().program(ROUND_PROGRAM)
        compiled = program.last_compiled if program is not None else None
        text = compiled.as_text() if compiled is not None else ""
    if records is None:
        from fedml_tpu.telemetry import get_tracer

        records = get_tracer().records()
    parts = None
    if trace.ops and text:
        parts = seconds_by_part(
            trace, instruction_op_names(text), load_rules())
    # a million intervals to merge in a 10 s trace: once for both joins
    busy = [trace_reduce.busy_intervals(trace, d) for d in trace.devices]
    ctx["scopes"] = {
        "parts": parts,
        "busy_s": sum(e - s for d in busy for s, e in d) / len(busy),
        "idle": idle_ms_by_phase(trace, records, busy[0])}
    return ctx["scopes"]


def part_time_pct(ctx: dict, part: str):
    """A part's share of device busy time, ``%``; ``None`` where there is
    nothing to read or (for a named part) nothing matched."""
    got = from_ctx(ctx)
    if not got["parts"] or got["busy_s"] <= 0:
        return None
    seconds = got["parts"].get(part, 0.0)
    if seconds <= 0 and part != UNATTRIBUTED:
        return None
    return 100.0 * seconds / got["busy_s"]


def idle_ms(ctx: dict, label: str):
    got = from_ctx(ctx)
    return None if got["idle"] is None else got["idle"][label]
