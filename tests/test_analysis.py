"""graftcheck (PR 12): semantic static analysis over the repo's invariants.

Per-pass fixture tests (seeded violation caught, clean twin not flagged),
suppression mechanics (``# graft: allow`` + ``analysis_baseline.txt``),
and the tier-1 acceptance: the repo-wide run is CLEAN and fast.  The
repo-wide test is the CI gate the ISSUE asks for — reverting any of this
PR's satellite bug fixes re-surfaces exactly that finding and fails it.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
import time

import pytest

from fedml_tpu.analysis import Repo, run_analysis
from fedml_tpu.analysis.passes import (
    donation,
    host_sync,
    jit_purity,
    lint as lint_pass,
    messages,
    span_names,
    threads,
)
from fedml_tpu.analysis.runner import BaselineError, load_baseline

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def make_repo(tmp_path, files):
    """Write ``{relpath: source}`` under tmp_path and model it as a Repo."""
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return Repo(str(tmp_path))


# -- jit-purity -------------------------------------------------------------

_JIT_IMPURE = """
    import time
    import jax
    import jax.numpy as jnp

    def _helper(x):
        return x * time.time()

    def impure_step(x):
        return _helper(x) + 1.0

    step = jax.jit(impure_step)
"""

_JIT_CLEAN = """
    import jax
    import jax.numpy as jnp

    def pure_step(x, key):
        return x + jax.random.normal(key, x.shape)

    step = jax.jit(pure_step)
"""


def test_jit_purity_catches_host_call_via_callee(tmp_path):
    repo = make_repo(tmp_path, {"fedml_tpu/a.py": _JIT_IMPURE})
    found = jit_purity.run(repo)
    assert len(found) == 1
    assert "time.time" in found[0].message
    assert found[0].pass_id == "jit-purity"


def test_jit_purity_clean_twin(tmp_path):
    repo = make_repo(tmp_path, {"fedml_tpu/a.py": _JIT_CLEAN})
    assert jit_purity.run(repo) == []


def test_jit_purity_sync_forcers_and_module_rng(tmp_path):
    repo = make_repo(tmp_path, {"fedml_tpu/a.py": """
        import jax
        import numpy as np

        def bad(x):
            y = float(x)          # sync on a traced param
            z = np.random.rand()  # module RNG
            return x.sum().item() + x.item() + y + z

        prog = jax.jit(bad)
    """})
    msgs = " | ".join(f.message for f in jit_purity.run(repo))
    assert "float() on traced value 'x'" in msgs
    assert "numpy RNG" in msgs
    assert "item()" in msgs


def test_jit_purity_static_argnums_exempt(tmp_path):
    # int() on a static (python-level) parameter is NOT a sync; the same
    # call on the traced parameter is — including when registered via
    # wrap_jit over an already-decorated function
    repo = make_repo(tmp_path, {"fedml_tpu/a.py": """
        import functools
        import jax

        @functools.partial(jax.jit, static_argnums=(1,))
        def sized(x, k):
            return x * int(k)

        prog = wrap_jit("compress/encode", sized)
    """})
    assert jit_purity.run(repo) == []


# -- donation ---------------------------------------------------------------

_DONATE_BAD = """
    import jax

    def f(a, b):
        return a + b

    prog = jax.jit(f, donate_argnums=(0,))

    def caller(x, y):
        out = prog(x, y)
        return out + x
"""

_DONATE_OK = """
    import jax

    def f(a, b):
        return a + b

    prog = jax.jit(f, donate_argnums=(0,))

    def caller(x, y):
        x = prog(x, y)
        return x + y
"""


def test_donation_read_after_donate(tmp_path):
    repo = make_repo(tmp_path, {"fedml_tpu/a.py": _DONATE_BAD})
    found = donation.run(repo)
    assert len(found) == 1
    assert "donated to 'prog'" in found[0].message


def test_donation_rebinding_is_safe(tmp_path):
    repo = make_repo(tmp_path, {"fedml_tpu/a.py": _DONATE_OK})
    assert donation.run(repo) == []


def test_donation_loop_without_rebinding(tmp_path):
    repo = make_repo(tmp_path, {"fedml_tpu/a.py": """
        import jax

        def f(a):
            return a * 2

        prog = jax.jit(f, donate_argnums=(0,))

        def looping(x):
            for _ in range(3):
                out = prog(x)
            return out

        def chained(x):
            for _ in range(3):
                x = prog(x)
            return x
    """})
    found = donation.run(repo)
    assert len(found) == 1  # `looping` flagged, `chained` rebinds
    assert "loop" in found[0].message


def test_donation_wrap_jit_site_and_self_attr(tmp_path):
    repo = make_repo(tmp_path, {"fedml_tpu/a.py": """
        import jax
        from fedml_tpu.telemetry import wrap_jit

        class T:
            def __init__(self, step):
                self._step = wrap_jit(
                    "llm/train_step",
                    jax.jit(step, donate_argnums=(0, 1)))

            def round(self, batch):
                self.params, self.opt = self._step(self.params, self.opt,
                                                   batch)
                return self.params

            def broken(self, batch):
                new_p, new_o = self._step(self.params, self.opt, batch)
                stale = self.params
                return new_p, new_o, stale
    """})
    found = donation.run(repo)
    # `round` rebinds both donated attributes in the donating statement
    # (safe); `broken` re-reads only self.params afterwards
    assert len(found) == 1
    assert "'self.params'" in found[0].message


# -- host-sync --------------------------------------------------------------

_SYNC_BAD = """
    def run_round(r):
        loss = _round_fn(r)
        rec = float(loss)
        probe = loss.item()
        return rec + probe
"""

_SYNC_OK = """
    def run_round(r, eval_round):
        loss = _round_fn(r)
        if eval_round:
            return float(loss)
        return None
"""


def test_host_sync_flags_unsanctioned(tmp_path):
    repo = make_repo(tmp_path,
                     {"fedml_tpu/simulation/sp/loop.py": _SYNC_BAD})
    found = host_sync.run(repo)
    msgs = " | ".join(f.message for f in found)
    assert "float() on device value 'loss'" in msgs
    assert "loss.item()" in msgs


def test_host_sync_guarded_is_sanctioned(tmp_path):
    repo = make_repo(tmp_path,
                     {"fedml_tpu/simulation/sp/loop.py": _SYNC_OK})
    assert host_sync.run(repo) == []


def test_host_sync_only_round_loop_files(tmp_path):
    # the same code outside the round-loop modules is not this pass's
    # business (the jit-purity pass governs jitted bodies instead)
    repo = make_repo(tmp_path, {"fedml_tpu/utils/misc.py": _SYNC_BAD})
    assert host_sync.run(repo) == []


# -- thread-safety ----------------------------------------------------------

_THREADS_BAD = """
    import threading

    class Worker:
        def __init__(self):
            self._lock = threading.Lock()
            self.count = 0

        def start(self):
            t = threading.Thread(target=self._loop, daemon=True)
            t.start()

        def _loop(self):
            self.count += 1

        def bump(self):
            self.count += 1
"""

_THREADS_OK = """
    import threading

    class Worker:
        def __init__(self):
            self._lock = threading.Lock()
            self.count = 0

        def start(self):
            t = threading.Thread(target=self._loop, daemon=True)
            t.start()

        def _loop(self):
            with self._lock:
                self.count += 1

        def bump(self):
            with self._lock:
                self.count += 1
"""


def test_thread_safety_unlocked_cross_thread_write(tmp_path):
    repo = make_repo(tmp_path, {"fedml_tpu/w.py": _THREADS_BAD})
    found = threads.run(repo)
    assert len(found) == 1
    assert "self.count" in found[0].message
    assert "_loop" in found[0].message


def test_thread_safety_locked_twin_clean(tmp_path):
    repo = make_repo(tmp_path, {"fedml_tpu/w.py": _THREADS_OK})
    assert threads.run(repo) == []


def test_thread_safety_lock_held_helper_and_comm_handlers(tmp_path):
    # two comm handlers share the receive thread (ONE logical
    # entrypoint, no finding); a helper whose every call site holds the
    # lock counts as lock-held even though its own body takes none
    repo = make_repo(tmp_path, {"fedml_tpu/m.py": """
        import threading

        class Manager:
            def __init__(self):
                self._lock = threading.Lock()
                self.last = None

            def register(self):
                self.register_message_receive_handler("a", self.handle_a)
                self.register_message_receive_handler("b", self.handle_b)

            def handle_a(self, msg):
                self.last = msg

            def handle_b(self, msg):
                self.last = msg

        class Locked:
            def __init__(self):
                self._lock = threading.Lock()
                self.n = 0

            def start(self):
                threading.Thread(target=self._loop, daemon=True).start()

            def _loop(self):
                with self._lock:
                    self._bump()

            def bump_public(self):
                with self._lock:
                    self._bump()

            def _bump(self):
                self.n += 1
    """})
    assert threads.run(repo) == []


def test_thread_safety_public_method_as_thread_target(tmp_path):
    # the flush()-as-target pattern: one PUBLIC method is both the
    # thread body and caller-facing API — that alone is two entrypoints
    repo = make_repo(tmp_path, {"fedml_tpu/d.py": """
        import threading

        class Daemon:
            def __init__(self):
                self._offset = 0

            def start(self):
                threading.Thread(target=self.flush, daemon=True).start()

            def flush(self):
                self._offset += 1
    """})
    found = threads.run(repo)
    assert len(found) == 1
    assert "self._offset" in found[0].message


# -- message-contract -------------------------------------------------------

_MSG_BAD = """
    from fedml_tpu.core.distributed.message import Message

    class Msgs:
        GOOD = "t.good"
        ORPHAN_SEND = "t.orphan_send"
        ORPHAN_HANDLER = "t.orphan_handler"

    class Peer:
        def register(self):
            self.register_message_receive_handler(Msgs.GOOD, self._h)
            self.register_message_receive_handler(
                Msgs.ORPHAN_HANDLER, self._h)

        def talk(self):
            self.send_message(Message(Msgs.GOOD, 0, 1))
            self.send_message(Message(Msgs.ORPHAN_SEND, 0, 1))
"""


def test_message_contract_orphans(tmp_path):
    repo = make_repo(tmp_path, {"fedml_tpu/p.py": _MSG_BAD})
    found = messages.run(repo)
    msgs = " | ".join(f.message for f in found)
    assert len(found) == 2
    assert "'t.orphan_send' is sent here but no peer registers" in msgs
    assert "handler registered for 't.orphan_handler'" in msgs
    assert "t.good" not in msgs


def test_message_contract_resolves_class_alias(tmp_path):
    # the PR 7 idiom: `M = InfMessage` then M.MSG_TYPE_X at both ends
    repo = make_repo(tmp_path, {"fedml_tpu/p.py": """
        from fedml_tpu.core.distributed.message import Message

        class M2:
            PING = "t2.ping"

        class Peer:
            def register(self):
                M = M2
                self.register_message_receive_handler(M.PING, self._h)

            def talk(self):
                self.send_message(Message(M2.PING, 0, 1))
    """})
    assert messages.run(repo) == []


# -- migrated passes (span-names / lint) ------------------------------------

def test_span_names_pass_on_fixture(tmp_path):
    repo = make_repo(tmp_path, {"fedml_tpu/t.py": """
        def f(tracer, reg):
            with tracer.span(f"round/{0}/Train"):
                pass
            reg.histogram("resilience/retry_ms").observe(1.0)
    """})
    found = span_names.run(repo)
    msgs = " | ".join(f.message for f in found)
    assert "violates the taxonomy" in msgs
    assert "not" in msgs and "histograms" in msgs


def test_span_names_shard_namespace_rules(tmp_path):
    """shard/* metrics are per-shard layout signals: one segment, gauge
    or counter only — mesh axes and program names ride labels."""
    repo = make_repo(tmp_path, {"fedml_tpu/t.py": """
        def f(reg):
            reg.gauge("shard/devices").set(4.0)
            reg.gauge("shard/llm/fused_round_cp/hbm").set(1.0)
            reg.histogram("shard/depth").observe(2.0)
    """})
    found = span_names.run(repo)
    msgs = " | ".join(f.message for f in found)
    assert "must be shard/<signal>" in msgs
    assert "not" in msgs and "histograms" in msgs
    assert "'shard/devices'" not in msgs  # the well-shaped gauge passes


def test_span_names_program_namespace_is_three_stages(tmp_path):
    """program/* spans are exactly a cataloged program's three stages;
    the program's name rides the `program` attribute, never the name."""
    repo = make_repo(tmp_path, {"fedml_tpu/t.py": """
        def f(tracer):
            with tracer.span("program/trace", program="llm/fused_round"):
                pass
            with tracer.span("program/lower"):
                pass
            with tracer.span("program/compile", cache="off"):
                pass
            with tracer.span("program/execute"):
                pass
            with tracer.span("program/llm/fused_round"):
                pass
    """})
    msgs = [f.message for f in span_names.run(repo)]
    assert len(msgs) == 2
    assert all("must be program/trace, program/lower or program/compile"
               in m for m in msgs)
    assert any("'program/execute'" in m for m in msgs)


def test_lint_pass_on_fixture(tmp_path):
    repo = make_repo(tmp_path, {"fedml_tpu/t.py": """
        import os
        import sys  # noqa

        def f():
            try:
                return os.getpid()
            except:
                print("boom")
    """})
    found = lint_pass.run(repo)
    msgs = " | ".join(f.message for f in found)
    assert "E722 bare except" in msgs
    assert "T201" in msgs
    assert "unused import 'sys'" not in msgs  # noqa honored


def test_shims_keep_historical_api():
    import importlib.util

    for tool, attrs in (("check_span_names", ("collect", "check",
                                              "normalize", "main")),
                        ("lint", ("check_file", "iter_py", "main"))):
        spec = importlib.util.spec_from_file_location(
            f"shim_{tool}", os.path.join(REPO, "tools", f"{tool}.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        for a in attrs:
            assert callable(getattr(mod, a)), (tool, a)
    # behavior parity: bad entries still produce path:line-prefixed strings
    spec = importlib.util.spec_from_file_location(
        "shim_span", os.path.join(REPO, "tools", "check_span_names.py"))
    span = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(span)
    bad = [("x.py", 3, "span", span.normalize("round/{r}/Train", True))]
    out = span.check(bad)
    assert len(out) == 1 and out[0].startswith("x.py:3: ")


# -- suppression: allow-comments + baseline ---------------------------------

def test_allow_comment_suppresses_with_justification(tmp_path):
    repo = make_repo(tmp_path, {"fedml_tpu/simulation/sp/loop.py": """
        def run_round(r):
            loss = _round_fn(r)
            # graft: allow(host-sync): fixture — deliberate sync
            return float(loss)
    """})
    result = run_analysis(str(tmp_path), passes=["host-sync"], repo=repo)
    assert result.findings == []
    assert len(result.suppressed_inline) == 1


def test_allow_comment_without_justification_is_a_finding(tmp_path):
    repo = make_repo(tmp_path, {"fedml_tpu/simulation/sp/loop.py": """
        def run_round(r):
            loss = _round_fn(r)
            return float(loss)  # graft: allow(host-sync)
    """})
    result = run_analysis(str(tmp_path), passes=["host-sync"], repo=repo)
    ids = {f.pass_id for f in result.findings}
    assert "suppression" in ids  # the naked allow is itself flagged
    assert "host-sync" not in ids  # ...but it still suppresses


def test_allow_comment_wrong_pass_does_not_suppress(tmp_path):
    repo = make_repo(tmp_path, {"fedml_tpu/simulation/sp/loop.py": """
        def run_round(r):
            loss = _round_fn(r)
            # graft: allow(donation): wrong pass id
            return float(loss)
    """})
    result = run_analysis(str(tmp_path), passes=["host-sync"], repo=repo)
    assert [f.pass_id for f in result.findings] == ["host-sync"]


def test_baseline_suppresses_and_goes_stale(tmp_path):
    repo = make_repo(tmp_path,
                     {"fedml_tpu/simulation/sp/loop.py": _SYNC_BAD})
    finding = host_sync.run(repo)[0]
    (tmp_path / "analysis_baseline.txt").write_text(
        f"{finding.key} :: fixture justification\n"
        "host-sync|fedml_tpu/simulation/sp/loop.py|gone :: was fixed\n")
    result = run_analysis(str(tmp_path), passes=["host-sync"], repo=repo)
    assert finding.key not in {f.key for f in result.findings}
    assert len(result.suppressed_baseline) == 1
    assert result.stale_baseline == [
        "host-sync|fedml_tpu/simulation/sp/loop.py|gone"]


def test_span_names_paths_repo_relative_and_waivable(tmp_path):
    # findings must key on repo-relative paths whatever --root is, or
    # allow/baseline/--changed plumbing silently stops matching
    src = """
        def f(tracer):
            with tracer.span(f"round/{0}/Train"):
                pass
    """
    repo = make_repo(tmp_path, {"fedml_tpu/t.py": src})
    found = span_names.run(repo)
    assert found and found[0].path == "fedml_tpu/t.py"
    repo2 = make_repo(tmp_path / "waived", {"fedml_tpu/t.py": src.replace(
        "with tracer.span",
        "# graft: allow(span-names): fixture waiver\n            "
        "with tracer.span")})
    result = run_analysis(str(tmp_path / "waived"),
                          passes=["span-names"], repo=repo2)
    assert result.findings == []
    assert len(result.suppressed_inline) == 1


def test_stale_baseline_scoped_to_executed_passes(tmp_path):
    repo = make_repo(tmp_path, {"fedml_tpu/w.py": _THREADS_BAD})
    finding = threads.run(repo)[0]
    (tmp_path / "analysis_baseline.txt").write_text(
        f"{finding.key} :: fixture justification\n")
    # a lint-only run must NOT call the thread-safety entry stale
    result = run_analysis(str(tmp_path), passes=["lint"], repo=repo)
    assert result.stale_baseline == []
    result = run_analysis(str(tmp_path), passes=["thread-safety"],
                          repo=repo)
    assert result.stale_baseline == []
    assert len(result.suppressed_baseline) == 1


def test_stacked_single_pass_allows_compose(tmp_path):
    repo = make_repo(tmp_path, {"fedml_tpu/simulation/sp/loop.py": """
        def run_round(r):
            loss = _round_fn(r)
            # graft: allow(donation): unrelated waiver stacked above
            # graft: allow(host-sync): fixture — deliberate sync
            return float(loss)
    """})
    result = run_analysis(str(tmp_path), passes=["host-sync"], repo=repo)
    assert result.findings == []
    # and in the other stacking order
    repo2 = make_repo(tmp_path / "b", {"fedml_tpu/simulation/sp/loop.py": """
        def run_round(r):
            loss = _round_fn(r)
            # graft: allow(host-sync): fixture — deliberate sync
            # graft: allow(donation): unrelated waiver stacked below
            return float(loss)
    """})
    result = run_analysis(str(tmp_path / "b"), passes=["host-sync"],
                          repo=repo2)
    assert result.findings == []


def test_lint_shim_survives_broken_package_import(tmp_path):
    # the old tools were stdlib-only: a syntax error in the fedml_tpu
    # import chain must yield an E999 report, not an import traceback
    import shutil

    scratch = tmp_path / "scratch"
    scratch.mkdir()
    shutil.copytree(os.path.join(REPO, "fedml_tpu"),
                    scratch / "fedml_tpu",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copytree(os.path.join(REPO, "tools"), scratch / "tools",
                    ignore=shutil.ignore_patterns("__pycache__"))
    runner_py = scratch / "fedml_tpu" / "runner.py"
    runner_py.write_text("def broken(:\n")
    proc = subprocess.run(
        [sys.executable, str(scratch / "tools" / "lint.py"), "fedml_tpu"],
        capture_output=True, text=True, cwd=str(scratch), check=False)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "E999 syntax error" in proc.stdout
    assert "Traceback" not in proc.stderr


def test_baseline_requires_justification(tmp_path):
    p = tmp_path / "analysis_baseline.txt"
    p.write_text("host-sync|fedml_tpu/a.py|msg\n")
    with pytest.raises(BaselineError):
        load_baseline(str(p))


def test_changed_only_filters_reporting(tmp_path):
    repo = make_repo(tmp_path, {
        "fedml_tpu/simulation/sp/loop.py": _SYNC_BAD,
        "fedml_tpu/w.py": _THREADS_BAD,
    })
    result = run_analysis(str(tmp_path), changed_only={"fedml_tpu/w.py"},
                          repo=repo)
    assert result.findings  # the thread finding survives the filter
    assert {f.path for f in result.findings} == {"fedml_tpu/w.py"}


# -- acceptance: the repo itself --------------------------------------------

def test_repo_wide_clean_and_under_budget():
    """The tier-1 gate: zero unsuppressed findings, no stale baseline
    entries, and the whole run inside the ~20s budget."""
    t0 = time.monotonic()
    result = run_analysis(REPO)
    elapsed = time.monotonic() - t0
    assert result.findings == [], "\n".join(
        f.render() for f in result.findings)
    assert result.stale_baseline == []
    assert elapsed < 20.0, f"graftcheck took {elapsed:.1f}s (budget ~20s)"
    # every pass actually ran over a real file set
    assert result.files > 200
    assert set(result.counts) >= {"jit-purity", "donation", "host-sync",
                                  "thread-safety", "message-contract",
                                  "span-names", "lint"}


def test_cli_json_schema():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "graftcheck.py"),
         "--json"],
        capture_output=True, text=True, cwd=REPO, check=False)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout.strip().splitlines()[-1])
    assert payload["schema"] == "graftcheck/v1"
    assert payload["ok"] is True
    assert payload["findings"] == []
    assert payload["files"] > 200
