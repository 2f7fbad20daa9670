"""Hierarchical spans with cross-process trace propagation.

Every span carries a ``(trace_id, span_id, parent_id)`` triple. Inside one
process the current span rides a ``contextvars.ContextVar``; across
processes the context travels as a header:

- comm messages (LOCAL/GRPC/TRPC/BROKER backends): a JSON-safe
  ``telemetry_ctx`` field injected into the message params by
  ``FedMLCommManager.send_message`` and re-activated around handler
  dispatch on the receiving rank;
- raw ``PubSubBroker`` frames: a binary envelope (magic + JSON header)
  prepended to the published body by ``BrokerClient`` and stripped on the
  subscriber side, so server-side and client-side spans of the same round
  stitch into one timeline.

Span naming follows the taxonomy ``round/<n>[/client/<id>]/<phase>`` for
round work and ``<subsystem>/<what>`` elsewhere; ``tools/
check_span_names.py`` lints the instrumented literals.

JAX compile-vs-execute split: one ``jax.monitoring`` listener
(:func:`install_jax_compile_listener`) attributes backend compile-or-load
seconds to whatever span is open when XLA compiles, so a span's
``compile_ms`` separates "first round pays the bridge" from steady-state
execution. A cataloged program's first call runs as three child spans,
``program/trace``, ``program/lower`` and ``program/compile``
(``profiling/catalog.py``); their times roll up into the span they ran
under as ``trace_ms``, ``lower_ms`` and ``compile_ms``, and its
``execute_ms`` is what is left.

One clock with the device trace: ``Tracer.span`` also opens a
``jax.profiler.TraceAnnotation`` of the same name for the same interval,
so every context-managed span of the repo sits on a host line of any
profiler capture (the benchmark's, ``TraceController``'s, an operator's
XProf/Perfetto view) above the device lines. ``begin()/end()`` pairs may
cross threads and stay in-memory only.
"""
from __future__ import annotations

import atexit
import collections
import contextlib
import contextvars
import json
import os
import struct
import threading
import time
import uuid
import weakref
from typing import Any, Dict, Iterator, List, Optional

from fedml_tpu.telemetry import flight_recorder
from fedml_tpu.telemetry.registry import get_registry

CTX_KEY = "telemetry_ctx"
_FRAME_MAGIC = b"\xf5TCX"


class TraceContext:
    __slots__ = ("trace_id", "span_id")

    def __init__(self, trace_id: str, span_id: str):
        self.trace_id = trace_id
        self.span_id = span_id

    def to_dict(self) -> Dict[str, str]:
        return {"trace_id": self.trace_id, "span_id": self.span_id}

    @classmethod
    def from_dict(cls, d: Dict[str, str]) -> "TraceContext":
        return cls(str(d["trace_id"]), str(d["span_id"]))

    def __repr__(self) -> str:  # pragma: no cover
        return f"TraceContext({self.trace_id}/{self.span_id})"


_current: "contextvars.ContextVar[Optional[_ActiveSpan]]" = contextvars.ContextVar(
    "fedml_telemetry_span", default=None
)


class _ActiveSpan:
    """Mutable in-flight span; becomes an immutable record at end()."""

    __slots__ = ("name", "trace_id", "span_id", "parent_id", "parent",
                 "started", "started_mono", "attrs", "remote_parent",
                 "placeholder", "compile_ms", "trace_ms", "lower_ms",
                 "duration_ms")

    def __init__(self, name: str, trace_id: str, parent_id: Optional[str],
                 remote_parent: bool, attrs: Dict[str, Any],
                 parent: "Optional[_ActiveSpan]" = None):
        self.name = name
        self.trace_id = trace_id
        self.span_id = uuid.uuid4().hex[:16]
        self.parent_id = parent_id
        self.parent = parent
        # wall clock for human-readable placement, monotonic for durations:
        # an NTP step mid-run shifts `started` but cannot corrupt the
        # measured length of the span
        self.started = time.time()
        self.started_mono = time.perf_counter()
        self.attrs = attrs
        self.remote_parent = remote_parent
        self.placeholder = False
        # backend compile-or-load: booked while this span is innermost, or
        # rolled up from a program/compile child; the children's durations
        self.compile_ms = 0.0
        self.trace_ms = 0.0
        self.lower_ms = 0.0
        self.duration_ms: Optional[float] = None  # set at end()

    def context(self) -> TraceContext:
        return TraceContext(self.trace_id, self.span_id)


def new_trace_id() -> str:
    return uuid.uuid4().hex


def current_context() -> Optional[TraceContext]:
    span = _current.get()
    return span.context() if span is not None else None


def activate_context(ctx: Optional[TraceContext]):
    """Adopt a remote context as the current parent; returns a reset token.

    The adopted context is represented as a zero-duration placeholder so
    child spans stitch to the remote span id without recording anything.
    """
    if ctx is None:
        return None
    holder = _ActiveSpan("remote", ctx.trace_id, None, True, {})
    holder.span_id = ctx.span_id
    holder.placeholder = True
    return _current.set(holder)


def deactivate_context(token) -> None:
    if token is not None:
        _current.reset(token)


# -- header propagation (comm-message params dict) ------------------------
def inject_context(params: Dict[str, Any]) -> None:
    ctx = current_context()
    if ctx is not None:
        params[CTX_KEY] = ctx.to_dict()


def extract_context(params: Dict[str, Any]) -> Optional[TraceContext]:
    raw = params.pop(CTX_KEY, None)
    if not isinstance(raw, dict) or "trace_id" not in raw:
        return None
    try:
        return TraceContext.from_dict(raw)
    except (KeyError, TypeError):
        return None


# -- frame propagation (raw broker bodies) ---------------------------------
def wrap_frame_body(body: bytes, ctx: Optional[TraceContext] = None) -> bytes:
    """Prepend the trace header to a pub/sub body (no-op without context).

    Layout: magic ‖ u16 header_len ‖ json(ctx) ‖ body. The broker routes
    bodies opaquely (Python and native C++ alike), so the envelope is
    invisible to it and to the wire protocol.
    """
    ctx = ctx or current_context()
    if ctx is None:
        return body
    header = json.dumps(ctx.to_dict()).encode()
    return _FRAME_MAGIC + struct.pack(">H", len(header)) + header + body


def unwrap_frame_body(body: bytes):
    """Split (ctx | None, original_body); bodies without the magic — or
    that merely start with the magic bytes by accident — pass through
    untouched, so un-instrumented publishers stay compatible."""
    if not body.startswith(_FRAME_MAGIC) or len(body) < 6:
        return None, body
    (hlen,) = struct.unpack(">H", body[4:6])
    if len(body) < 6 + hlen:
        return None, body
    try:
        ctx = TraceContext.from_dict(json.loads(body[6 : 6 + hlen]))
    except (ValueError, KeyError, UnicodeDecodeError):
        return None, body
    return ctx, body[6 + hlen :]


# -- jax compile attribution ----------------------------------------------
_jax_listener_installed = False
_jax_listener_lock = threading.Lock()

_CACHE_REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


def _on_jax_duration(event: str, duration_secs: float, **kw) -> None:
    # JAX's backend_compile event wraps compile_or_get_cached: on a warm
    # cache it times the executable's LOAD, not a compile
    if "backend_compile" not in event:
        return
    ms = duration_secs * 1e3
    get_registry().histogram("jax/compile_ms").observe(ms)
    span = _current.get()
    if span is not None:
        span.compile_ms += ms
    # the catalog books it to the program whose call is on this stack
    from fedml_tpu.telemetry.profiling.catalog import book_compile

    book_compile(ms)


def _on_jax_event(event: str, **kw) -> None:
    # a miss is an entry written; a hit is a compile skipped
    if "cache_hit" in event:
        get_registry().counter("jax/compile_cache_hits").inc()
    elif "cache_miss" in event:
        get_registry().counter("jax/compile_cache_misses").inc()
    elif "compilation_cache" in event:
        get_registry().counter("jax/compile_cache_requests").inc()
    span = _current.get()
    if span is not None and span.name == "program/compile":
        # JAX records cache_misses only for an entry it writes, so "miss"
        # is a request that no hit followed: asked, then compiled
        if event == _CACHE_REQUEST:
            span.attrs["cache"] = "miss"
        elif event == _CACHE_HIT:
            span.attrs["cache"] = "hit"


def install_jax_compile_listener() -> None:
    """The process's one ``jax.monitoring`` listener over compiles (a
    duration callback and an event callback).

    Installed once per process (lazily, by the first Tracer, catalog or
    device-stats sampler); a few ns when no compile happens. Each backend
    compile-or-load lands in the open span's ``compile_ms``, the
    ``jax/compile_ms`` histogram and the catalog record of the program on
    the caller's stack; each compilation-cache event in the
    ``jax/compile_cache_{hits,misses,requests}`` counters and, under a
    ``program/compile`` span, its ``cache`` attribute (``hit``, ``miss``,
    or ``off`` where the cache was not asked).
    """
    global _jax_listener_installed
    with _jax_listener_lock:
        if _jax_listener_installed:
            return
        try:
            import jax.monitoring
        except ImportError:  # pragma: no cover - jax is a hard dep in-tree
            return
        jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)
        jax.monitoring.register_event_listener(_on_jax_event)
        _jax_listener_installed = True


# one atexit hook over weak refs: tracers stay collectable, and the exit
# flush covers however many instances are still alive
_live_tracers: "weakref.WeakSet[Tracer]" = weakref.WeakSet()


def _flush_live_tracers() -> None:
    for t in list(_live_tracers):
        try:
            t.flush()
        except OSError:  # pragma: no cover - sink dir gone at exit
            pass


atexit.register(_flush_live_tracers)


# -- span listeners --------------------------------------------------------
# Process-global observers of completed span/event records — the live
# tracing plane (SpanStreamer) taps here so remote nodes can ship their
# spans without the tracer knowing anything about transports. Listener
# exceptions are swallowed: observability must never break the traced code.
_span_listeners: List[Any] = []
_span_listeners_lock = threading.Lock()


def add_span_listener(fn) -> None:
    """Register ``fn(record: dict)`` to observe every completed span and
    every point event recorded by any tracer in this process."""
    with _span_listeners_lock:
        if fn not in _span_listeners:
            _span_listeners.append(fn)


def remove_span_listener(fn) -> None:
    with _span_listeners_lock:
        try:
            _span_listeners.remove(fn)
        except ValueError:
            pass


def _notify_span_listeners(rec: Dict) -> None:
    with _span_listeners_lock:
        listeners = list(_span_listeners)
    for fn in listeners:
        try:
            fn(rec)
        except Exception:  # noqa: BLE001 - listeners must never raise out
            pass


# a cataloged program's first call (profiling/catalog.py) is three spans
# that report to the span they ran under: tracing and lowering by their
# duration, compile-or-load by what the listener booked (the rest of the
# compile span, building the executable's Python object, stays execute)
_STAGE_TOTALS = {"program/trace": "trace_ms", "program/lower": "lower_ms",
                 "program/compile": "compile_ms"}


# a memory-only tracer keeps this many of its newest records (a fused LLM
# round leaves six spans: several hundred rounds)
RING_RECORDS = 4096


class Tracer:
    """Span factory + buffered JSONL sink.

    With a ``sink_dir``, completed spans buffer in memory and flush to
    ``<sink_dir>/<filename>`` when the buffer passes ``buffer_limit``, on
    ``flush()``, and at interpreter exit — a crash loses at most one
    buffer, not the run. Without one (the default ``get_tracer()`` until
    ``configure()``), the tracer is a bounded ring of its newest
    ``RING_RECORDS`` records, which ``records()`` returns: the in-memory
    copy a reader in the same process uses.
    """

    def __init__(self, sink_dir: Optional[str] = None,
                 filename: str = "spans.jsonl", buffer_limit: int = 256,
                 service: str = ""):
        self._dir = sink_dir
        self._filename = filename
        self._limit = max(int(buffer_limit), 1)
        self.service = service
        self._lock = threading.Lock()
        self._records = ([] if sink_dir is not None
                         else collections.deque(maxlen=RING_RECORDS))
        install_jax_compile_listener()
        from jax.profiler import TraceAnnotation

        self._annotation = TraceAnnotation
        _live_tracers.add(self)

    @property
    def sink_dir(self) -> Optional[str]:
        return self._dir

    # -- span lifecycle ---------------------------------------------------
    def begin(self, name: str, **attrs: Any) -> _ActiveSpan:
        parent = _current.get()
        if parent is not None:
            # only the DIRECT child of an adopted remote context is marked
            # stitched; its own descendants are ordinary local spans
            span = _ActiveSpan(name, parent.trace_id, parent.span_id,
                               parent.placeholder, attrs, parent)
        else:
            span = _ActiveSpan(name, new_trace_id(), None, False, attrs)
        return span

    def end(self, span: _ActiveSpan, ended: Optional[float] = None) -> Dict:
        if ended is None:
            # duration from the monotonic clock; `ended` derived so the
            # ended - started == duration invariant survives for readers
            duration_ms = (time.perf_counter() - span.started_mono) * 1e3
            ended = span.started + duration_ms / 1e3
        else:
            # explicit end times are wall-clock by contract (backfill,
            # tests) — keep the historical wall math for them
            duration_ms = (ended - span.started) * 1e3
        rec = {
            "name": span.name,
            "trace_id": span.trace_id,
            "span_id": span.span_id,
            "parent_id": span.parent_id,
            "started": span.started,
            "mono": span.started_mono,
            "ended": ended,
            "duration_ms": duration_ms,
        }
        span.duration_ms = duration_ms
        staged = span.trace_ms + span.lower_ms
        if staged:
            rec["trace_ms"] = span.trace_ms
            rec["lower_ms"] = span.lower_ms
        if span.compile_ms:
            rec["compile_ms"] = span.compile_ms
        if staged or span.compile_ms:
            rec["execute_ms"] = max(
                duration_ms - staged - span.compile_ms, 0.0)
        total = _STAGE_TOTALS.get(span.name)
        if total is not None and span.parent is not None:
            took = span.compile_ms if total == "compile_ms" else duration_ms
            setattr(span.parent, total, getattr(span.parent, total) + took)
        if span.remote_parent:
            rec["remote_parent"] = True
        if self.service:
            rec["service"] = self.service
        if span.attrs:
            rec["attrs"] = span.attrs
        self._keep(rec)
        # a condensed copy rides the flight-recorder ring so a crash dump
        # shows the last spans even when the sink buffer died with them
        flight_recorder.on_span(rec)
        _notify_span_listeners(rec)
        return rec

    def event(self, name: str, **attrs: Any) -> Dict:
        """Record a zero-duration point event at the current instant.

        Point records land in the same JSONL sink as spans but carry
        ``point: true`` and no ``duration_ms``, so ``load_spans``-based
        consumers (report phases, stragglers) skip them while the trace
        assembler can use them as precise causal markers — e.g. the
        ``comm/send``/``comm/recv`` pairs that clock alignment matches.
        """
        rec: Dict[str, Any] = {
            "name": name,
            "point": True,
            "ts": time.time(),
            "mono": time.perf_counter(),
        }
        ctx = current_context()
        if ctx is not None:
            rec["trace_id"] = ctx.trace_id
            rec["span_id"] = ctx.span_id
        if self.service:
            rec["service"] = self.service
        if attrs:
            rec["attrs"] = attrs
        self._keep(rec)
        _notify_span_listeners(rec)
        return rec

    @contextlib.contextmanager
    def span(self, name: str, **attrs: Any) -> Iterator[_ActiveSpan]:
        s = self.begin(name, **attrs)
        token = _current.set(s)
        # the same interval on the profiler's clock: a host-line event in
        # any capture that is running (about a microsecond when none is)
        try:
            with self._annotation(name, span_id=s.span_id,
                                  parent_id=s.parent_id or ""):
                yield s
        finally:
            _current.reset(token)
            self.end(s)

    # -- sink -------------------------------------------------------------
    def _keep(self, rec: Dict) -> None:
        overflow = None
        with self._lock:
            self._records.append(rec)
            # a memory-only tracer is a ring: the deque drops its oldest
            if self._dir is not None and len(self._records) >= self._limit:
                overflow = self._records
                self._records = []
        if overflow is not None:
            self._write(overflow)

    def records(self) -> List[Dict]:
        with self._lock:
            return list(self._records)

    def _write(self, records: List[Dict]) -> Optional[str]:
        if self._dir is None or not records:
            return None
        os.makedirs(self._dir, exist_ok=True)
        path = os.path.join(self._dir, self._filename)
        with open(path, "a") as f:
            for rec in records:
                f.write(json.dumps(rec, default=str) + "\n")
        return path

    def flush(self) -> Optional[str]:
        if self._dir is None:
            return None  # nowhere to land them: the ring keeps them
        with self._lock:
            records, self._records = self._records, []
        return self._write(records)


_default_tracer: Optional[Tracer] = None
_default_lock = threading.Lock()


def get_tracer() -> Tracer:
    """The process-global tracer (memory-only until configure() points it
    at a run dir)."""
    global _default_tracer
    with _default_lock:
        if _default_tracer is None:
            _default_tracer = Tracer()
        return _default_tracer


def configure(run_dir: str, service: str = "") -> Tracer:
    """Bind the global tracer to a run dir (idempotent per dir). Also
    points the flight recorder's crash dump at the same dir, so every
    engine that lands spans gets the black box for free."""
    global _default_tracer
    with _default_lock:
        t = _default_tracer
        if t is None or t._dir != run_dir:
            t = Tracer(sink_dir=run_dir, service=service)
            _default_tracer = t
    flight_recorder.bind(run_dir)
    return t


def configure_from_args(args: Any, service: str = "") -> Tracer:
    """Derive the sink dir from run args — same layout core/mlops uses:
    ``<log_file_dir>/run_<run_id>/``. Also applies the run's deep-trace
    budget knobs (``trace_max_captures`` / ``trace_byte_budget`` /
    ``trace_rounds``) to the process TraceController. ``service`` stamps
    this process's records with its node identity, which is what lets
    trace assembly tell nodes apart in a shared run dir."""
    run_id = str(getattr(args, "run_id", "0") or "0")
    base = str(getattr(args, "log_file_dir", "") or ".fedml_logs")
    tracer = configure(os.path.join(base, f"run_{run_id}"), service=service)
    from fedml_tpu.telemetry.profiling import trace as _trace

    _trace.configure_from_args(args)
    return tracer


def flush_run() -> Optional[str]:
    """Land the global tracer's spans, a registry snapshot, AND the
    program-catalog snapshot (``programs.jsonl``) in the run dir (no-op
    for an unconfigured, memory-only tracer). The one call a training
    loop needs at the end of ``train()``."""
    from fedml_tpu.telemetry.registry import get_registry as _reg

    tracer = get_tracer()
    tracer.flush()
    if tracer.sink_dir is None:
        return None
    from fedml_tpu.telemetry.profiling import get_catalog

    get_catalog().flush_jsonl(tracer.sink_dir)
    return _reg().flush_jsonl(tracer.sink_dir)


def reset_tracer() -> None:
    """Drop the global tracer (test isolation)."""
    global _default_tracer
    with _default_lock:
        _default_tracer = None
