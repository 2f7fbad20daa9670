"""Program catalog — every hot-path XLA program, named and accounted.

The catalog answers the question the doctor and the multichip plan both
need: *which compiled program* owns each reported second and byte. Every
hot-path jitted function (mesh fused round, sp local-train step, the
compression codecs, secagg ``unmask_finalize``, hierarchy chunk programs,
the serving decode/prefill family) registers under a stable name via
:func:`wrap_jit`; the returned :class:`CatalogedProgram` then OWNS
execution:

- first call per input signature: ``jitted.trace(*args)``, ``.lower()``,
  ``.compile()`` — each stage a span of the process tracer
  (``program/trace``, ``program/lower``, ``program/compile``, attribute
  ``program=<name>``; the last also ``cache`` = ``hit`` / ``miss`` /
  ``off`` for the persistent compilation cache), children of whatever
  span is open — and exactly ONE backend compile (the jit path and the
  AOT path do not share an executable cache, so letting both run would
  double-compile); the executable's ``cost_analysis()`` FLOPs /
  bytes-accessed plus ``memory_analysis()`` argument/output/temp HBM
  come free off the same object;
- subsequent calls: a last-used fastpath straight into the compiled
  executable. ``Compiled.__call__`` validates pytree + avals itself and
  raises ``TypeError`` *before* dispatch (donated buffers still alive),
  so the fastpath needs no per-call signature hashing — a mismatch falls
  back to the keyed-variant slow path, and a brand-new signature becomes
  a new variant (that is the recompile counter treedef churn is read off).

A call whose signature the AOT staging API rejects (``TypeError``) falls
back permanently to the raw jitted callable for that signature; a
compiler error is raised to the caller, once. The
catalog records the fallback and the program still gets compile-time
attribution via the process's one ``jax.monitoring`` listener
(``spans.install_jax_compile_listener``: compiles that fire while a
cataloged call is on this thread's stack are booked to that program;
all others land in ``uncataloged``, so
``sum(per-program compile events) + uncataloged == jax/compile_ms count``
holds exactly — the histogram and this booking are one listener).

Snapshots persist as ``<run_dir>/programs.jsonl`` (one line per program,
rewritten whole at each flush) and as ``profile/*`` registry instruments
so the live plane streams them (see :mod:`..live`).
"""
from __future__ import annotations

import contextvars
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

from fedml_tpu.telemetry.registry import get_registry

__all__ = [
    "CatalogedProgram",
    "ProgramCatalog",
    "ProgramRecord",
    "get_catalog",
    "pump_profile_gauges",
    "reset_catalog",
    "wrap_jit",
]

# the program whose wrapped call is on this thread's stack — the
# jax.monitoring compile listener attributes backend-compile events here
_PROGRAM_VAR: "contextvars.ContextVar[Optional[str]]" = contextvars.ContextVar(
    "fedml_profile_program", default=None)

_ENV_DISABLE = "FEDML_PROFILE"  # "0" disables the catalog process-wide


def _enabled_from_env() -> bool:
    return os.environ.get(_ENV_DISABLE, "1") not in ("0", "false", "off")


class _Variant:
    """One compiled input signature of a cataloged program."""

    __slots__ = ("compiled", "statics", "fallback", "flops", "bytes_accessed")

    def __init__(self, compiled=None, statics: Tuple = (),
                 fallback: bool = False, flops: float = 0.0,
                 bytes_accessed: float = 0.0):
        self.compiled = compiled
        self.statics = statics
        self.fallback = fallback
        self.flops = flops
        self.bytes_accessed = bytes_accessed


class ProgramRecord:
    """Mutable accounting for one named program (all variants)."""

    def __init__(self, name: str, multi_shape: bool = False):
        self.name = name
        self.multi_shape = bool(multi_shape)
        self.flops = 0.0            # latest-variant cost_analysis flops
        self.bytes_accessed = 0.0   # latest-variant bytes accessed
        self.argument_bytes = 0.0
        self.output_bytes = 0.0
        self.temp_bytes = 0.0
        self.peak_hbm_bytes = 0.0   # max over variants of arg+out+temp
        self.generated_code_bytes = 0.0
        self.compile_ms = 0.0       # backend compile-or-load (listener)
        self.compile_wall_ms = 0.0  # program/{trace,lower,compile} spans' sum
        self.compile_events = 0     # backend_compile events booked here
        self.n_signatures = 0       # distinct compiled input signatures
        self.calls = 0
        self.fallback_calls = 0
        self.analysis_error: Optional[str] = None
        self.treedef: Optional[str] = None
        self.first_call_ts: Optional[float] = None
        self.phase_calls: Dict[str, int] = {}
        # mesh/sharding spec of the latest compiled variant (None for
        # single-device programs): {"axes": {name: size}, "n_shards": N,
        # "in_shardings": [...], "out_shardings": [...]}. When n_shards
        # > 1 every byte figure above (argument/output/temp/peak HBM) is
        # PER SHARD — XLA's memory_analysis plans one device's slice —
        # which is exactly the number the per-device admission gate and
        # the doctor's headroom verdict must compare against the limit.
        self.mesh_spec: Optional[Dict[str, Any]] = None

    def to_dict(self) -> Dict[str, Any]:
        from fedml_tpu.telemetry.profiling.roofline import (
            arithmetic_intensity,
            classify,
        )

        ai = arithmetic_intensity(self.flops, self.bytes_accessed)
        return {
            "name": self.name,
            "flops": self.flops,
            "bytes_accessed": self.bytes_accessed,
            "argument_bytes": self.argument_bytes,
            "output_bytes": self.output_bytes,
            "temp_bytes": self.temp_bytes,
            "peak_hbm_bytes": self.peak_hbm_bytes,
            "generated_code_bytes": self.generated_code_bytes,
            "compile_ms": round(self.compile_ms, 3),
            "compile_wall_ms": round(self.compile_wall_ms, 3),
            "compile_events": self.compile_events,
            "n_signatures": self.n_signatures,
            "recompiles": max(self.n_signatures - 1, 0),
            "multi_shape": self.multi_shape,
            "calls": self.calls,
            "fallback_calls": self.fallback_calls,
            "analysis_error": self.analysis_error,
            "treedef": self.treedef,
            "phase_calls": dict(self.phase_calls),
            "mesh_spec": self.mesh_spec,
            "arithmetic_intensity": ai,
            "roofline_class": classify(ai) if ai is not None else None,
        }


def _phase_of(span_name: Optional[str], memo: Dict[str, str]) -> str:
    """Normalize the enclosing span's name to a stable phase key
    (``round/3/client/7/train`` → ``round/<n>/client/<id>/train``)."""
    if not span_name:
        return "unattributed"
    hit = memo.get(span_name)
    if hit is not None:
        return hit
    from fedml_tpu.telemetry.report import normalize_name

    phase = normalize_name(span_name)
    if len(memo) < 4096:  # runs are rounds×phases; cap pathological churn
        memo[span_name] = phase
    return phase


def _shard_token(leaf) -> Any:
    """A hashable token for a leaf's multi-device sharding, else None.

    Single-device and host leaves all map to None so the signature of
    every pre-existing (unsharded) call is unchanged — only arrays laid
    out over a >1-device mesh (the per-shard aggregation path, fsdp
    params) key distinct compiled variants. Without this, a program
    called first unsharded then sharded at the same shapes would reuse
    the wrong executable.
    """
    s = getattr(leaf, "sharding", None)
    if s is None or getattr(s, "mesh", None) is None:
        return None
    try:
        if s.mesh.size <= 1:
            return None
        return s  # NamedSharding is hashable
    except Exception:  # pragma: no cover - exotic sharding type
        return None


def _sig_of(args: Sequence[Any], kwargs: Dict[str, Any],
            static_argnums: Tuple[int, ...]) -> Tuple:
    """Hashable input signature: static args by value, array leaves by
    (shape, dtype[, mesh sharding]), other hashables by (type, value)."""
    import jax

    parts: List[Any] = []
    for i, a in enumerate(args):
        if i in static_argnums:
            parts.append(("s", a))
            continue
        leaves, treedef = jax.tree_util.tree_flatten(a)
        sig = []
        for leaf in leaves:
            shape = getattr(leaf, "shape", None)
            if shape is not None:
                tok = _shard_token(leaf)
                sig.append((tuple(shape), str(leaf.dtype)) if tok is None
                           else (tuple(shape), str(leaf.dtype), tok))
            else:
                sig.append((type(leaf),))  # python scalar: dynamic weak arg
        parts.append((treedef, tuple(sig)))
    if kwargs:
        for k in sorted(kwargs):
            leaves, treedef = jax.tree_util.tree_flatten(kwargs[k])
            parts.append((k, treedef, tuple(
                (tuple(x.shape), str(x.dtype)) if hasattr(x, "shape")
                else (type(x),) for x in leaves)))
    return tuple(parts)


def _mesh_spec_of(compiled) -> Optional[Dict[str, Any]]:
    """The mesh/sharding spec of a compiled executable, or None.

    Introspected off the executable itself (``input_shardings`` /
    ``output_shardings``) so EVERY cataloged program that runs sharded —
    the fsdp LLM round, the shard_map mesh simulator, the per-shard
    fused aggregation — records its partition layout without any caller
    plumbing. Single-device programs (no mesh, or a 1-device mesh)
    record nothing: ``mesh_spec is None`` means the byte figures are
    whole-program, not per-shard.
    """
    import jax

    from fedml_tpu.utils.jax_compat import pspec_str, sharding_mesh_axes

    try:
        in_shardings = jax.tree_util.tree_leaves(compiled.input_shardings)
        out_shardings = jax.tree_util.tree_leaves(compiled.output_shardings)
    except Exception:
        return None
    axes: Dict[str, int] = {}
    for s in in_shardings + out_shardings:
        for name, size in sharding_mesh_axes(s).items():
            axes[name] = max(axes.get(name, 1), size)
    n_shards = 1
    for size in axes.values():
        n_shards *= size
    if n_shards <= 1:
        return None

    def _specs(shardings, cap: int = 16) -> List[str]:
        seen: List[str] = []
        for s in shardings:
            label = pspec_str(s)
            if label not in seen:
                seen.append(label)
            if len(seen) >= cap:
                break
        return seen

    return {
        "axes": axes,
        "n_shards": n_shards,
        "in_shardings": _specs(in_shardings),
        "out_shardings": _specs(out_shardings),
    }


class CatalogedProgram:
    """Callable wrapper that owns AOT compile + execution of one program."""

    def __init__(self, catalog: "ProgramCatalog", name: str, jitted,
                 static_argnums: Tuple[int, ...] = (),
                 multi_shape: bool = False):
        self._catalog = catalog
        self._name = name
        self._jitted = jitted
        self._static = tuple(int(i) for i in static_argnums)
        self._variants: Dict[Tuple, _Variant] = {}
        self._last: Optional[_Variant] = None
        self._lock = threading.Lock()
        self.record = catalog._record(name, multi_shape=multi_shape)

    # expose the underlying jit for callers that need AOT stages directly
    @property
    def jitted(self):
        return self._jitted

    def lower(self, *args, **kwargs):
        """AOT-stage passthrough so wrapped programs keep the jit API."""
        return self._jitted.lower(*args, **kwargs)

    @property
    def last_compiled(self):
        """The executable the most recent call ran (``None`` before the
        first call, or when that signature fell back to the raw jit) —
        ``as_text()`` / ``memory_analysis()`` without a second compile."""
        last = self._last
        return None if last is None or last.fallback else last.compiled

    @property
    def name(self) -> str:
        return self._name

    def _dynamic(self, args: Sequence[Any]) -> List[Any]:
        if not self._static:
            return list(args)
        return [a for i, a in enumerate(args) if i not in self._static]

    def _statics_match(self, variant: _Variant, args: Sequence[Any]) -> bool:
        if not self._static:
            return True
        for (i, v) in variant.statics:
            if i >= len(args):
                return False
            a = args[i]
            if a is not v and a != v:
                return False
        return True

    def _note_call(self, variant: Optional[_Variant]) -> None:
        rec = self.record
        from fedml_tpu.telemetry import spans as _spans

        span = _spans._current.get()
        phase = _phase_of(span.name if span is not None else None,
                          self._catalog._phase_memo)
        # one short lock covers calls/phase/rate totals: cataloged
        # programs run from concurrent threads (serving engine, prefetch
        # worker) and unlocked read-modify-writes would drop counts the
        # MFU gauges are computed from (~100 ns, inside the <1% seam)
        cat = self._catalog
        with cat._rate_lock:
            rec.calls += 1
            if rec.first_call_ts is None:
                rec.first_call_ts = time.time()
            rec.phase_calls[phase] = rec.phase_calls.get(phase, 0) + 1
            if variant is not None and not variant.fallback:
                cat._flops_total += variant.flops
                cat._bytes_total += variant.bytes_accessed
                if variant.flops and not rec.flops:
                    # a REUSED variant calling into a fresh (re-homed
                    # after reset_catalog) record re-lands its analysis:
                    # flops/bytes are properties of the compiled program,
                    # not of the accounting epoch — without this, any
                    # earlier run that already compiled this signature
                    # would leave the new epoch's record claiming
                    # flops=0 for a program that demonstrably ran
                    rec.flops = variant.flops
                    rec.bytes_accessed = variant.bytes_accessed

    def __call__(self, *args, **kwargs):
        cat = self._catalog
        if not cat.enabled:
            return self._jitted(*args, **kwargs)
        token = _PROGRAM_VAR.set(self._name)
        try:
            last = self._last
            if last is not None and not kwargs and not last.fallback \
                    and self._statics_match(last, args):
                try:
                    out = last.compiled(*self._dynamic(args))
                except (TypeError, ValueError):
                    # pytree/aval mismatch (TypeError) and input-sharding
                    # mismatch (ValueError) are both raised BEFORE
                    # dispatch (no donation happened) — take the keyed
                    # slow path, which keys per-mesh-sharding variants
                    out = self._slow_call(args, kwargs)
                else:
                    self._note_call(last)
                return out
            return self._slow_call(args, kwargs)
        finally:
            _PROGRAM_VAR.reset(token)

    # -- slow path: keyed variant lookup / first-compile ------------------
    def _slow_call(self, args: Sequence[Any], kwargs: Dict[str, Any]):
        try:
            key = _sig_of(args, kwargs, self._static)
        except TypeError:
            # unhashable static/leaf — permanent fallback territory
            self.record.fallback_calls += 1
            self._note_call(None)
            return self._jitted(*args, **kwargs)
        with self._lock:
            variant = self._variants.get(key)
        if variant is None:
            variant = self._compile_variant(key, args, kwargs)
        self._last = variant
        if variant.fallback:
            self.record.fallback_calls += 1
            self._note_call(None)
            return self._jitted(*args, **kwargs)
        out = variant.compiled(*self._dynamic(args), **kwargs)
        self._note_call(variant)
        return out

    def _compile_variant(self, key: Tuple, args: Sequence[Any],
                         kwargs: Dict[str, Any]) -> _Variant:
        from fedml_tpu.telemetry.spans import get_tracer

        rec = self.record
        statics = tuple((i, args[i]) for i in self._static if i < len(args))
        tracer, name = get_tracer(), self._name
        try:
            with tracer.span("program/trace", program=name) as trace_s:
                traced = self._jitted.trace(*args, **kwargs)
            with tracer.span("program/lower", program=name) as lower_s:
                lowered = traced.lower()
            # the compile listener turns "off" into "miss" or "hit"
            with tracer.span("program/compile", program=name,
                             cache="off") as compile_s:
                compiled = lowered.compile()
        except TypeError as e:
            # AOT staging rejected the call's signature (an argument the
            # staged API cannot take) — fall back to the raw jit forever.
            # Anything the COMPILER says (out of memory, a kernel it will
            # not partition, a bad sharding) propagates: retrying it
            # through the jit would pay the whole compile a second time
            # to raise the same error.
            variant = _Variant(statics=statics, fallback=True)
            with self._lock:
                self._variants[key] = variant
                rec.analysis_error = f"{type(e).__name__}: {e}"[:200]
            return variant
        wall_ms = (trace_s.duration_ms + lower_s.duration_ms
                   + compile_s.duration_ms)
        variant = _Variant(compiled=compiled, statics=statics)
        self._analyze(compiled, variant)
        try:
            import jax

            rec.treedef = str(jax.tree_util.tree_structure(
                (tuple(args), kwargs)))[:400]
        except Exception:  # pragma: no cover - structure of a lowerable tree
            pass
        with self._lock:
            self._variants[key] = variant
            rec.compile_wall_ms += wall_ms
            rec.n_signatures += 1
            if rec.n_signatures > 1:
                get_registry().counter(
                    "profile/recompiles",
                    labels={"program": self._name}).inc()
        return variant

    def _analyze(self, compiled, variant: _Variant) -> None:
        rec = self.record
        try:
            cost = compiled.cost_analysis() or {}
            variant.flops = float(cost.get("flops", 0.0) or 0.0)
            variant.bytes_accessed = float(
                cost.get("bytes accessed", 0.0) or 0.0)
        except Exception as e:
            rec.analysis_error = f"cost_analysis: {type(e).__name__}"[:200]
        try:
            mem = compiled.memory_analysis()
            arg = float(getattr(mem, "argument_size_in_bytes", 0) or 0)
            out = float(getattr(mem, "output_size_in_bytes", 0) or 0)
            tmp = float(getattr(mem, "temp_size_in_bytes", 0) or 0)
            alias = float(getattr(mem, "alias_size_in_bytes", 0) or 0)
            gen = float(getattr(mem, "generated_code_size_in_bytes", 0) or 0)
            rec.argument_bytes = arg
            rec.output_bytes = out
            rec.temp_bytes = tmp
            rec.generated_code_bytes = gen
            # live-at-peak upper bound: args + outputs + temporaries minus
            # donated aliasing — the number HBM planning reads
            rec.peak_hbm_bytes = max(rec.peak_hbm_bytes,
                                     arg + out + tmp - alias)
        except Exception as e:
            rec.analysis_error = f"memory_analysis: {type(e).__name__}"[:200]
        spec = _mesh_spec_of(compiled)
        if spec is not None or rec.mesh_spec is None:
            rec.mesh_spec = spec
        if variant.flops:
            rec.flops = variant.flops
            rec.bytes_accessed = variant.bytes_accessed


class ProgramCatalog:
    """Process-wide registry of cataloged programs."""

    def __init__(self, enabled: Optional[bool] = None):
        self.enabled = _enabled_from_env() if enabled is None else enabled
        self._records: Dict[str, ProgramRecord] = {}
        self._programs: Dict[str, CatalogedProgram] = {}
        self._lock = threading.Lock()
        self._rate_lock = threading.Lock()  # per-call counters/totals
        self._phase_memo: Dict[str, str] = {}
        self._flops_total = 0.0
        self._bytes_total = 0.0
        self.uncataloged_compiles = 0
        self.uncataloged_compile_ms = 0.0
        self._pump_t0: Optional[float] = None
        self._pump_flops = 0.0
        from fedml_tpu.telemetry.spans import install_jax_compile_listener

        install_jax_compile_listener()

    # -- registration -----------------------------------------------------
    def _record(self, name: str, multi_shape: bool = False) -> ProgramRecord:
        with self._lock:
            rec = self._records.get(name)
            if rec is None:
                rec = self._records[name] = ProgramRecord(
                    name, multi_shape=multi_shape)
            return rec

    def wrap(self, name: str, jitted,
             static_argnums: Tuple[int, ...] = (),
             multi_shape: bool = False) -> CatalogedProgram:
        prog = CatalogedProgram(self, name, jitted,
                                static_argnums=static_argnums,
                                multi_shape=multi_shape)
        with self._lock:
            self._programs[name] = prog
        return prog

    def program(self, name: str) -> Optional[CatalogedProgram]:
        """The live wrapper registered under ``name`` (latest wins)."""
        with self._lock:
            return self._programs.get(name)

    # -- compile attribution (spans' jax.monitoring listener) --------------
    def on_compile_event(self, ms: float) -> None:
        name = _PROGRAM_VAR.get()
        if name is None:
            self.uncataloged_compiles += 1
            self.uncataloged_compile_ms += ms
            return
        rec = self._record(name)
        rec.compile_events += 1
        rec.compile_ms += ms

    # -- reads -------------------------------------------------------------
    def records(self) -> List[ProgramRecord]:
        with self._lock:
            return sorted(self._records.values(), key=lambda r: r.name)

    def snapshot(self) -> List[Dict[str, Any]]:
        return [r.to_dict() for r in self.records()]

    def programs_summary(self) -> Dict[str, Dict[str, float]]:
        """The compact name → {flops, bytes, peak-HBM} map BENCH json and
        bench_compare consume."""
        out: Dict[str, Dict[str, float]] = {}
        for rec in self.records():
            if rec.calls == 0 and rec.n_signatures == 0:
                continue
            out[rec.name] = {
                "flops": rec.flops,
                "bytes_accessed": rec.bytes_accessed,
                "peak_hbm_bytes": rec.peak_hbm_bytes,
                "compile_ms": round(rec.compile_ms, 3),
                "calls": rec.calls,
                "recompiles": max(rec.n_signatures - 1, 0),
                # per-shape-variant programs are exempt from recompile
                # regression flags downstream (bench_compare, doctor)
                "multi_shape": rec.multi_shape,
                # per-shard layout (None = single-device program); when
                # present, peak_hbm_bytes above is one shard's plan
                "mesh_spec": rec.mesh_spec,
            }
        return out

    # -- sinks -------------------------------------------------------------
    def flush_jsonl(self, run_dir: str,
                    filename: str = "programs.jsonl") -> Optional[str]:
        """Rewrite the per-run program catalog snapshot (one line per
        program — a snapshot, not an append stream). Programs that never
        ran in this catalog's lifetime (registered wrappers from other
        engines in the process) are not part of this run."""
        rows = [r for r in self.snapshot()
                if r["calls"] or r["compile_events"] or r["n_signatures"]]
        if not rows:
            return None
        import jax

        try:
            dev = jax.devices()[0]
            device_kind, platform = dev.device_kind, dev.platform
        except Exception:  # pragma: no cover - backend init failure
            device_kind = platform = None
        os.makedirs(run_dir, exist_ok=True)
        path = os.path.join(run_dir, filename)
        tmp = path + ".tmp"
        ts = time.time()
        with open(tmp, "w") as f:
            for row in rows:
                f.write(json.dumps({
                    "ts": ts, "device_kind": device_kind,
                    "platform": platform, **row}, default=str) + "\n")
        os.replace(tmp, path)
        # deliberately NO pump_gauges here: flush runs AFTER the live
        # plane's final frame, and mutating profile/* gauges then would
        # break the collector==post-hoc exact-totals invariant — the
        # device-stats phase tick is the only gauge refresher
        return path

    def pump_gauges(self) -> None:
        """Land the catalog state in ``profile/*`` registry instruments so
        the live plane streams it (counter/gauge only — lint-enforced)."""
        from fedml_tpu.telemetry.profiling.roofline import (
            arithmetic_intensity,
            device_peaks,
            ridge_point,
        )

        reg = get_registry()
        records = self.records()
        reg.gauge("profile/programs").set(float(len(records)))
        reg.gauge("profile/uncataloged_compiles").set(
            float(self.uncataloged_compiles))
        for rec in records:
            labels = {"program": rec.name}
            reg.gauge("profile/flops", labels=labels).set(rec.flops)
            reg.gauge("profile/bytes_accessed", labels=labels).set(
                rec.bytes_accessed)
            reg.gauge("profile/peak_hbm_bytes", labels=labels).set(
                rec.peak_hbm_bytes)
            reg.gauge("profile/compile_ms", labels=labels).set(
                rec.compile_ms)
            reg.gauge("profile/calls", labels=labels).set(float(rec.calls))
            if rec.mesh_spec:
                # shard/* namespace: per-shard layout levels the live
                # plane streams next to profile/* (lint: gauge/counter
                # only, one segment, program rides the label)
                reg.gauge("shard/n_shards", labels=labels).set(
                    float(rec.mesh_spec["n_shards"]))
                reg.gauge("shard/per_shard_hbm_bytes", labels=labels).set(
                    rec.peak_hbm_bytes)
        # rolling achieved rate since the last pump → live MFU + roofline
        now = time.perf_counter()
        peaks = device_peaks()
        ridge = ridge_point(peaks)
        ai = arithmetic_intensity(self._flops_total, self._bytes_total)
        if ai is not None:
            reg.gauge("profile/ai").set(ai)
            reg.gauge("profile/ridge").set(ridge)
            reg.gauge("profile/hbm_bound").set(1.0 if ai < ridge else 0.0)
        if self._pump_t0 is not None:
            dt = now - self._pump_t0
            dflops = self._flops_total - self._pump_flops
            if dt > 1e-3 and dflops > 0:
                rate = dflops / dt
                reg.gauge("profile/flops_per_s").set(rate)
                if peaks[0]:
                    reg.gauge("profile/mfu").set(rate / peaks[0])
        self._pump_t0 = now
        self._pump_flops = self._flops_total


_catalog: Optional[ProgramCatalog] = None
_catalog_lock = threading.Lock()


def book_compile(ms: float) -> None:
    """The compile listener's booking into the CURRENT global catalog
    (read at event time, so registry/test resets stay honest)."""
    cat = _catalog
    if cat is not None:
        cat.on_compile_event(ms)


def get_catalog() -> ProgramCatalog:
    global _catalog
    with _catalog_lock:
        if _catalog is None:
            _catalog = ProgramCatalog()
        return _catalog


def reset_catalog() -> None:
    """Drop the process-global catalog (test isolation). Already-wrapped
    programs keep their compiled variants (recompiling every test would
    be the real regression) but re-home their accounting into the fresh
    catalog on next call."""
    global _catalog
    with _catalog_lock:
        old, _catalog = _catalog, ProgramCatalog()
        if old is not None:
            # re-home live wrappers: fresh records, same compiled variants
            for name, prog in old._programs.items():
                prog._catalog = _catalog
                prog.record = _catalog._record(
                    name, multi_shape=prog.record.multi_shape)
                _catalog._programs[name] = prog


def wrap_jit(name: str, jitted, static_argnums: Tuple[int, ...] = (),
             multi_shape: bool = False) -> CatalogedProgram:
    """Register ``jitted`` in the process catalog under ``name``.

    ``static_argnums`` must mirror the jit's own static argnums (the AOT
    executable is called with them stripped). ``multi_shape=True`` marks
    programs that legitimately compile one variant per input shape (the
    serving ``decode_group`` family) so the doctor's treedef-churn verdict
    skips them.
    """
    return get_catalog().wrap(name, jitted, static_argnums=static_argnums,
                              multi_shape=multi_shape)


def pump_profile_gauges() -> None:
    """Refresh ``profile/*`` gauges from the current catalog (cheap no-op
    when nothing registered) — called from the device-stats sampler so
    every phase sample also refreshes live MFU/roofline."""
    cat = _catalog
    if cat is not None and cat._records:
        cat.pump_gauges()
