"""span-names — the telemetry taxonomy lint, as an analysis pass.

This is ``tools/check_span_names.py`` migrated onto the shared core:
the scanning/normalization/shape rules are byte-identical (the tool is
now a shim over this module — ``collect``/``check``/``normalize`` keep
their signatures and output so the existing tier-1 wiring and
``tests/test_telemetry.py`` run unmodified), and ``run(repo)`` adapts
the same checks to :class:`~fedml_tpu.analysis.core.Repo` findings,
reusing the already-loaded sources.
"""
from __future__ import annotations

import os
import re
from typing import List, Tuple

from fedml_tpu.analysis.core import Finding, Repo

PASS_ID = "span-names"

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
ROOTS = ("fedml_tpu",)

_SPAN_CALL = re.compile(
    r"\.(?:span|begin)\(\s*(?:\n\s*)?(f?)\"([^\"]+)\"")
_METRIC_CALL = re.compile(
    r"\.(counter|gauge|histogram)\(\s*(?:\n\s*)?(f?)\"([^\"]+)\"")
_SEGMENT = re.compile(r"^(?:[a-z0-9_]+|<[a-z_]+>)$")
_ROUND_SHAPE = re.compile(
    r"^round/<v>(?:/client/<v>)?/[a-z0-9_]+$")
# compression spans are exactly the two codec phases — anything else
# under compress/ is taxonomy drift
_COMPRESS_SHAPE = re.compile(r"^compress/(?:encode|decode)$")
# a cataloged program's first call is exactly its three stages
# (profiling/catalog.py); the program's name rides the `program` attr
_PROGRAM_SHAPE = re.compile(r"^program/(?:trace|lower|compile)$")
# run-health namespaces: one segment after the prefix, per-entity
# dimensions (client id, phase) ride LABELS, never the name — and memory
# readings are instantaneous by definition, so mem/* must be gauges
_MEM_SHAPE = re.compile(r"^mem/[a-z0-9_]+$")
_HEALTH_SHAPE = re.compile(r"^health/[a-z0-9_]+$")
# resilience namespace: same one-segment rule (client ids, chaos actions
# and backends are labels); counters or gauges only — retry/reconnect/
# quorum signals are occurrence counts, not latency distributions
_RESILIENCE_SHAPE = re.compile(r"^resilience/[a-z0-9_]+$")
# crash-anywhere durability: the journal/restart signals are append and
# replay occurrence counts — COUNTERS only. A gauge here would let a
# restart silently zero the evidence the doctor's recovery section
# reads, and a histogram breaks the bounded live-frame contract.
_DURABILITY_SHAPE = re.compile(
    r"^resilience/(?:journal_[a-z0-9_]+|restarts|checkpoints_pruned)$")
# hierarchical-federation namespace: tier/<depth>/<signal> — exactly one
# interpolated tier depth then one signal segment (node/client ids are
# event fields, never name segments); counters or gauges only
_TIER_SHAPE = re.compile(r"^tier/<v>/[a-z0-9_]+$")
# live serving plane: serve/* spans are exactly the three swap phases
# (staging, the flip, the publisher's encode+send); serving/* metrics are
# one signal segment after the prefix — the endpoint id rides a label
_SERVE_SPAN_SHAPE = re.compile(r"^serve/(?:stage|swap|publish)$")
_SERVING_SHAPE = re.compile(r"^serving/[a-z0-9_]+$")
# request lifecycle: req/* spans are exactly the per-request stages the
# serving engine materializes at retirement (the whole request, its
# admission queue wait, prefill, decode, and a swap-stall sub-span
# pinned to the stalled stream) — span-only; the request's aggregate
# metrics live under serving/* (ttft_ms, tpot_ms, tokens_per_s)
_REQ_SPAN_SHAPE = re.compile(r"^req/(?:request|queue|prefill|decode|stall)$")
# live telemetry plane: live/* is the stream/collector meta-namespace
# (frames, seq gaps, alerts, scrapes) — one signal segment; node/job/rule
# dimensions ride labels. Metric-only: the plane never opens spans.
_LIVE_SHAPE = re.compile(r"^live/[a-z0-9_]+$")
# secure aggregation: secagg/* is metric-only (the masked encode/decode
# phases ride the existing compress/* spans); one signal segment, and
# counters only — every secagg signal is a protocol occurrence count
_SECAGG_SHAPE = re.compile(r"^secagg/[a-z0-9_]+$")
# job plane: sched/* is the supervision/preemption namespace — metric
# only, one signal segment (run/job/node ids ride event fields in
# sched_event records, never name segments); counters or gauges only —
# restart/preempt/reschedule signals are occurrence counts, queue depths
# are levels, neither is a latency distribution (MTTR is a bench metric,
# not a histogram)
_SCHED_SHAPE = re.compile(r"^sched/[a-z0-9_]+$")
# update integrity: integrity/* is the containment namespace (screen
# drops, quarantine, rollbacks, non-finite wire refusals) — metric-only
# (the screen/robust-agg programs live in the catalog as
# integrity/<name> PROGRAM names, not spans), one signal segment
# (clients/rounds/reasons ride integrity_event fields); counters or
# gauges only — screen/rollback signals are occurrence counts, the
# quarantine population is a level, neither is a distribution
_INTEGRITY_SHAPE = re.compile(r"^integrity/[a-z0-9_]+$")
# performance attribution: profile/* is the program-catalog namespace —
# metric-only (catalog programs are NOT spans; their names live in the
# `program` label), one signal segment, counter/gauge only (flops/bytes/
# HBM readings are levels, capture/recompile signals are counts — a
# histogram here would violate the bounded-frame live-plane contract)
_PROFILE_SHAPE = re.compile(r"^profile/[a-z0-9_]+$")
# multichip sharding: shard/* is the per-shard layout namespace (shard
# counts, per-shard HBM, depth-reduction occurrences on the virtual
# mesh) — metric-only (program names ride the `program` label exactly
# as profile/*), one signal segment, counter/gauge only — shard counts
# and per-shard byte plans are levels, guard trips are occurrence
# counts, neither is a distribution
_SHARD_SHAPE = re.compile(r"^shard/[a-z0-9_]+$")
# quantized residency: quant/* is the 4-bit/int8 base-weight namespace
# (packed-base bytes, packed-leaf counts) — metric-only (the pack/
# dequant-matmul programs live in the catalog as quant/<name> PROGRAM
# names, not spans), one signal segment (formats/blocks ride labels);
# counters or gauges only — packed footprints are levels, pack events
# are occurrence counts, neither is a distribution
_QUANT_SHAPE = re.compile(r"^quant/[a-z0-9_]+$")
# federated analytics: fa/* is the sketch-round namespace (rounds
# closed, quorum closes, deadline fires, stale/screened submissions,
# aborts, heavy-hitter recall, the accounted DP epsilon) — metric-only
# (an analytics round's spans keep their round/* names; the fused merge
# keeps compress/*), one signal segment (task/tier ride labels);
# counters or gauges only — round/drop signals are occurrence counts,
# recall/epsilon readings are levels, neither is a distribution
_FA_SHAPE = re.compile(r"^fa/[a-z0-9_]+$")
# causal tracing: tracepath/* is the span-stream/critical-path meta-
# namespace (frames, merged records, seq gaps, the latest round's
# critical phase/share) — metric-only (the traced spans themselves keep
# their own round/*, comm/* names), one signal segment (node/job ride
# labels); counters or gauges only — frame/record signals are occurrence
# counts, critical-phase readings are levels, and a histogram would
# break the bounded live-frame contract
_TRACEPATH_SHAPE = re.compile(r"^tracepath/[a-z0-9_]+$")


def normalize(literal: str, is_fstring: bool) -> str:
    if is_fstring:
        literal = re.sub(r"\{[^}]*\}", "<v>", literal)
    # literal numeric ids (docstring examples, fixed round 0 spans) are the
    # runtime shape of an interpolated id — same placeholder
    return re.sub(r"(?<=/)\d+(?=/|$)", "<v>", literal)


def _scan(path: str, src: str, out: list) -> None:
    for m in _SPAN_CALL.finditer(src):
        lineno = src[: m.start()].count("\n") + 1
        out.append((path, lineno, "span",
                    normalize(m.group(2), bool(m.group(1)))))
    for m in _METRIC_CALL.finditer(src):
        lineno = src[: m.start()].count("\n") + 1
        out.append((path, lineno, m.group(1),
                    normalize(m.group(3), bool(m.group(2)))))


def iter_py():
    for root in ROOTS:
        for base, dirs, files in os.walk(os.path.join(REPO, root)):
            dirs[:] = [d for d in dirs if d not in ("__pycache__", ".git")]
            for fn in files:
                if fn.endswith(".py"):
                    yield os.path.join(base, fn)


def collect():
    """[(path, lineno, kind, name)] for every instrumented literal."""
    out = []
    for path in sorted(iter_py()):
        with open(path, encoding="utf-8") as f:
            src = f.read()
        _scan(path, src, out)
    return out


def _check_structured(entries) -> List[Tuple[str, int, str]]:
    """[(relpath, lineno, message)] — the rule engine behind check()."""
    problems: List[Tuple[str, int, str]] = []
    metric_kinds = {}
    for path, lineno, kind, name in entries:
        rel = os.path.relpath(path, REPO) if os.path.isabs(path) else path
        where = f"{rel}:{lineno}"

        def bad(msg: str, rel=rel, lineno=lineno) -> None:
            problems.append((rel, lineno, msg))

        segments = name.split("/")
        if not all(_SEGMENT.match(s) for s in segments):
            bad(f"{kind} name {name!r} violates the taxonomy "
                "(lowercase [a-z0-9_] segments joined by '/')")
            continue
        if kind == "span" and name.startswith("round/"):
            if not _ROUND_SHAPE.match(name):
                bad(f"span {name!r} must follow "
                    "round/<n>[/client/<id>]/<phase>")
        if kind == "span" and name.startswith("compress/"):
            if not _COMPRESS_SHAPE.match(name):
                bad(f"span {name!r} must be compress/encode "
                    "or compress/decode")
        if kind == "span" and name.startswith("program/"):
            if not _PROGRAM_SHAPE.match(name):
                bad(f"span {name!r} must be program/trace, "
                    "program/lower or program/compile")
        if kind == "span" and name.startswith(
                ("mem/", "health/", "resilience/", "tier/", "live/",
                 "secagg/", "profile/", "sched/", "integrity/",
                 "tracepath/", "shard/", "quant/", "fa/")):
            bad(f"{name!r} — mem/, health/, resilience/, tier/, "
                "live/, secagg/, profile/, sched/, integrity/, "
                "tracepath/, shard/, quant/ and fa/ are metric "
                "namespaces, not span names")
        if kind == "span" and name.startswith("serve/"):
            if not _SERVE_SPAN_SHAPE.match(name):
                bad(f"span {name!r} must be serve/stage, "
                    "serve/swap or serve/publish")
        if kind != "span" and name.startswith("serve/"):
            bad(f"{kind} {name!r} — serve/ is the live-plane "
                "span namespace; its metrics live under serving/")
        if kind == "span" and name.startswith("req/"):
            if not _REQ_SPAN_SHAPE.match(name):
                bad(f"span {name!r} must be req/request, req/queue, "
                    "req/prefill, req/decode or req/stall")
        if kind != "span" and name.startswith("req/"):
            bad(f"{kind} {name!r} — req/ is the request-lifecycle "
                "span namespace; its aggregate metrics live under "
                "serving/")
        if kind != "span" and name.startswith("serving/"):
            if not _SERVING_SHAPE.match(name):
                bad(f"{kind} {name!r} must be serving/<signal> "
                    "(one segment; the endpoint id rides a label)")
        if kind != "span" and name.startswith("mem/"):
            if kind != "gauge":
                bad(f"{kind} {name!r} — mem/* readings are "
                    "instantaneous and must be gauges")
            elif not _MEM_SHAPE.match(name):
                bad(f"gauge {name!r} must be mem/<reading> "
                    "(one segment; device/phase go in labels)")
        if kind != "span" and name.startswith("health/"):
            if not _HEALTH_SHAPE.match(name):
                bad(f"{kind} {name!r} must be health/<signal> "
                    "(one segment; client ids go in labels)")
        if kind != "span" and name.startswith("resilience/"):
            if not _RESILIENCE_SHAPE.match(name):
                bad(f"{kind} {name!r} must be resilience/<signal> "
                    "(one segment; clients/actions/backends go in labels)")
            elif kind == "histogram":
                bad(f"{kind} {name!r} — resilience/* signals are "
                    "occurrence counts (counter) or levels (gauge), not "
                    "histograms")
            elif _DURABILITY_SHAPE.match(name) and kind != "counter":
                bad(f"{kind} {name!r} — durability journal/restart "
                    "signals are append/replay occurrence counts; "
                    "counters only")
        if kind != "span" and name.startswith("tier/"):
            if not _TIER_SHAPE.match(name):
                bad(f"{kind} {name!r} must be tier/<depth>/"
                    "<signal> (one depth segment, one signal segment; "
                    "node/client ids ride event fields)")
            elif kind == "histogram":
                bad(f"{kind} {name!r} — tier/* signals are "
                    "occurrence counts (counter) or levels (gauge), not "
                    "histograms")
        if kind != "span" and name.startswith("live/"):
            if not _LIVE_SHAPE.match(name):
                bad(f"{kind} {name!r} must be live/<signal> "
                    "(one segment; node/job/rule dimensions ride labels)")
        if kind != "span" and name.startswith("profile/"):
            if not _PROFILE_SHAPE.match(name):
                bad(f"{kind} {name!r} must be profile/<signal> "
                    "(one segment; program names and capture triggers "
                    "ride labels)")
            elif kind == "histogram":
                bad(f"{kind} {name!r} — profile/* signals are "
                    "levels (gauge) or occurrence counts (counter), not "
                    "histograms")
        if kind != "span" and name.startswith("shard/"):
            if not _SHARD_SHAPE.match(name):
                bad(f"{kind} {name!r} must be shard/<signal> "
                    "(one segment; program names and mesh axes ride "
                    "labels)")
            elif kind == "histogram":
                bad(f"{kind} {name!r} — shard/* signals are "
                    "levels (gauge) or occurrence counts (counter), not "
                    "histograms")
        if kind != "span" and name.startswith("integrity/"):
            if not _INTEGRITY_SHAPE.match(name):
                bad(f"{kind} {name!r} must be integrity/<signal> "
                    "(one segment; clients/rounds/reasons ride "
                    "integrity_event fields)")
            elif kind == "histogram":
                bad(f"{kind} {name!r} — integrity/* signals are "
                    "occurrence counts (counter) or levels (gauge), not "
                    "histograms")
        if kind != "span" and name.startswith("sched/"):
            if not _SCHED_SHAPE.match(name):
                bad(f"{kind} {name!r} must be sched/<signal> "
                    "(one segment; run/job/node ids ride sched_event "
                    "fields)")
            elif kind == "histogram":
                bad(f"{kind} {name!r} — sched/* signals are "
                    "occurrence counts (counter) or levels (gauge), not "
                    "histograms")
        if kind != "span" and name.startswith("quant/"):
            if not _QUANT_SHAPE.match(name):
                bad(f"{kind} {name!r} must be quant/<signal> "
                    "(one segment; formats and block sizes ride labels)")
            elif kind == "histogram":
                bad(f"{kind} {name!r} — quant/* signals are "
                    "levels (gauge) or occurrence counts (counter), not "
                    "histograms")
        if kind != "span" and name.startswith("fa/"):
            if not _FA_SHAPE.match(name):
                bad(f"{kind} {name!r} must be fa/<signal> "
                    "(one segment; task/tier dimensions ride labels)")
            elif kind == "histogram":
                bad(f"{kind} {name!r} — fa/* signals are occurrence "
                    "counts (counter) or levels (gauge), not "
                    "histograms")
        if kind != "span" and name.startswith("tracepath/"):
            if not _TRACEPATH_SHAPE.match(name):
                bad(f"{kind} {name!r} must be tracepath/<signal> "
                    "(one segment; node/job dimensions ride labels)")
            elif kind == "histogram":
                bad(f"{kind} {name!r} — tracepath/* signals are "
                    "occurrence counts (counter) or levels (gauge), not "
                    "histograms")
        if kind != "span" and name.startswith("secagg/"):
            if not _SECAGG_SHAPE.match(name):
                bad(f"{kind} {name!r} must be secagg/<signal> "
                    "(one segment; rounds/clients/tiers ride event "
                    "fields)")
            elif kind != "counter":
                bad(f"{kind} {name!r} — secagg/* signals are "
                    "protocol occurrence counts; counters only")
        if kind != "span":
            prev = metric_kinds.get(name)
            if prev is not None and prev[0] != kind:
                bad(f"metric {name!r} registered as {kind} but "
                    f"already a {prev[0]} at {prev[1]}")
            else:
                metric_kinds.setdefault(name, (kind, where))
    return problems


def check(entries):
    """Historical API: problem strings, ``path:line: message``."""
    return [f"{rel}:{lineno}: {msg}"
            for rel, lineno, msg in _check_structured(entries)]


_DUP_REF = re.compile(r"(registered as \w+ but already a \w+ at .+):\d+$")


def run(repo: Repo) -> List[Finding]:
    # feed repo-relative paths (file.rel) so findings carry the same
    # paths the runner's allow/baseline/--changed plumbing keys on,
    # whatever --root the analysis runs against
    entries: list = []
    for file in repo.package_files():
        _scan(file.rel, file.src, entries)
    # the duplicate-kind message embeds the first registration's
    # `path:line` (kept byte-identical in the shim's check()); baseline
    # keys are line-number-free by contract, so the Finding variant
    # drops the line
    return [Finding(PASS_ID, rel, lineno, _DUP_REF.sub(r"\1", msg))
            for rel, lineno, msg in _check_structured(entries)]


def main() -> int:
    entries = collect()
    problems = check(entries)
    for p in problems:
        print(p)  # noqa: T201 (CLI output)
    if problems:
        print(f"\n{len(problems)} problem(s)")  # noqa: T201 (CLI output)
        return 1
    print(f"span-name lint clean ({len(entries)} instrumented names)")  # noqa: T201 (CLI output)
    return 0
