"""Master agent — multi-node run orchestration + the supervising job plane.

Parity target: ``master/server_runner.py`` (``FedMLServerRunner`` :68 —
``run`` :427 drives a run across edges, ``callback_start_train`` :1462;
status aggregation back from the slaves). Re-design: the master keeps a
node registry fed by broker heartbeats, fans a multi-rank job out as one
run per node (each rank gets FEDML_RANK/FEDML_NUM_RANKS env — the
TPU-era replacement for the reference's MQTT-dispatched train configs),
aggregates per-rank status FSMs into one job status, detects dead nodes
by heartbeat loss, and pulls every rank's logs into one run view.

Job-plane semantics (preemptible capacity):

* **preemption** — :meth:`drain_node` SIGTERM-quiesces every run on a
  node (``preempt_run`` verb to the node agent) and, for *durable* jobs,
  reschedules each preempted rank onto a surviving node where it resumes
  from its journal/checkpoints. A node agent may also preempt locally on
  a reclaim notice (``drain_node`` wire message): the master reacts to
  the PREEMPTED status report the same way, so reschedule-and-resume
  works whichever side noticed the reclaim first.
* **node loss** — a node silent past ``node_loss_deadline_s`` (tracked by
  the PR 5 :class:`~fedml_tpu.resilience.liveness.PeerLiveness`) has its
  RUNNING durable ranks declared lost and rescheduled onto survivors;
  non-durable ranks go FAILED at the (shorter) heartbeat timeout exactly
  as before. A lost node that comes back is readmitted, and any
  superseded run it still reports RUNNING is told to stop.
* **admission** — rescheduling (and initial placement) is gated on the
  job's peak-HBM figure (``computing.peak_hbm_bytes``, or read from a
  PR 10 ``programs.jsonl`` via ``computing.programs_jsonl``) against the
  target node's advertised ``hbm_bytes_limit``, so a resumed job can't
  land on a node without headroom.

Job status semantics:
  RUNNING  while any active rank is non-terminal and no rank has failed
           (a PREEMPTED rank awaiting reschedule counts as in-flight)
  FINISHED when ALL active ranks finished
  FAILED   as soon as any active rank FAILED/EXCEPTION, or a rank could
           not be rescheduled
  KILLED   after stop_job()
"""
from __future__ import annotations

import logging
import threading
import time
import uuid
from typing import Dict, List, Optional, Set

from fedml_tpu.core.distributed.communication.broker_agent import (
    BrokerJsonAgent,
    PeerRegistry,
)
from fedml_tpu.core.mlops.status import RunStatus
from fedml_tpu.resilience.liveness import PeerLiveness
from fedml_tpu.scheduler.job_yaml import JobSpec
from fedml_tpu.scheduler.supervision import peak_hbm_from_programs, sched_event

logger = logging.getLogger(__name__)


def job_hbm_demand(spec: JobSpec) -> float:
    """Per-rank peak-HBM admission figure for a job: the explicit
    ``computing.peak_hbm_bytes``, else the max over a referenced PR 10
    ``programs.jsonl`` catalog, else 0 (unknown → unconstrained)."""
    comp = spec.computing or {}
    explicit = float(comp.get("peak_hbm_bytes", 0) or 0)
    if explicit:
        return explicit
    ref = comp.get("programs_jsonl")
    if ref:
        return float(peak_hbm_from_programs(str(ref)) or 0.0)
    return 0.0


class JobView:
    """Aggregated state of one multi-rank job."""

    def __init__(self, job_id: str, ranks: Dict[str, str],
                 spec: Optional[JobSpec] = None,
                 rank_env: Optional[Dict[str, Dict[str, str]]] = None):
        self.job_id = job_id
        self.ranks = ranks  # run_id → node_id
        self.rank_status: Dict[str, str] = {r: RunStatus.QUEUED for r in ranks}
        self.rank_rc: Dict[str, Optional[int]] = {r: None for r in ranks}
        self.rank_env: Dict[str, Dict[str, str]] = dict(rank_env or {})
        self.logs: Dict[str, str] = {}
        self.stopped = False
        self.spec = spec
        self.durable = bool(spec.durable) if spec is not None else False
        self.hbm_demand = job_hbm_demand(spec) if spec is not None else 0.0
        # runs replaced by a rescheduled successor: excluded from the job
        # status aggregation, remembered so a returning node's stale
        # RUNNING report can be told to stop
        self.superseded: Set[str] = set()
        self.resched_map: Dict[str, str] = {}   # old run_id → new run_id
        self.resched_count: Dict[str, int] = {}  # base run_id → attempts
        self.resched_refused: Set[str] = set()   # no admissible node
        self.lost_pending: Dict[str, float] = {}  # run_id → declared-lost ts

    def active_statuses(self) -> Dict[str, str]:
        return {r: s for r, s in self.rank_status.items()
                if r not in self.superseded}

    @property
    def status(self) -> str:
        active = self.active_statuses()
        statuses = set(active.values())
        if self.stopped:
            return RunStatus.KILLED
        # PREEMPTED is in-flight ONLY while a reschedule can still
        # supersede it; a preempted rank that can never resume — the job
        # is not durable (nothing to resume), or its reschedule was
        # refused (no admissible node / budget exhausted) — is a failure,
        # or wait_job would report RUNNING forever
        unresumable = any(
            s == RunStatus.PREEMPTED
            and (not self.durable
                 or (r in self.resched_refused
                     and r not in self.lost_pending))
            for r, s in active.items())
        if statuses & {RunStatus.FAILED, RunStatus.EXCEPTION} or unresumable:
            return RunStatus.FAILED
        if RunStatus.KILLED in statuses:
            return RunStatus.KILLED
        if statuses == {RunStatus.FINISHED}:
            return RunStatus.FINISHED
        # RESTARTING (agent-local backoff) is likewise in-flight
        return RunStatus.RUNNING

    @property
    def is_terminal(self) -> bool:
        return self.status in RunStatus.TERMINAL

    def describe(self) -> Dict:
        return {
            "job_id": self.job_id,
            "status": self.status,
            "durable": self.durable,
            "ranks": [
                {"run_id": rid, "node_id": self.ranks[rid],
                 "status": self.rank_status[rid],
                 "returncode": self.rank_rc[rid],
                 "superseded": rid in self.superseded}
                for rid in sorted(self.ranks)
            ],
            "rescheduled": dict(self.resched_map),
        }


class MasterAgent(BrokerJsonAgent):
    def __init__(self, broker_host: str, broker_port: int,
                 cluster: str = "default", node_timeout_s: float = 5.0,
                 node_loss_deadline_s: Optional[float] = None,
                 max_reschedules: int = 3,
                 reschedule_patience_s: float = 30.0, store=None):
        super().__init__(broker_host, broker_port)
        self.cluster = cluster
        self._store = store  # lazily created for OTA pushes
        self.registry = PeerRegistry(node_timeout_s)
        # node-loss deadline: dark (heartbeat timeout) fails non-durable
        # ranks fast; LOST (silent this much longer) reschedules durable
        # ones — the longer window rides out broker hiccups and GC pauses
        # that are not a reclaimed node
        self.node_loss_deadline_s = float(
            node_loss_deadline_s if node_loss_deadline_s is not None
            else 3.0 * node_timeout_s)
        self.liveness = PeerLiveness(silent_after_s=self.node_loss_deadline_s)
        self.max_reschedules = int(max_reschedules)
        # a LOST rank with momentarily no admissible survivor (every node
        # busy, dark, or without HBM headroom) retries each sweep for this
        # long before the rank permanently fails — a transient capacity
        # dip must not permafail a resumable job
        self.reschedule_patience_s = float(reschedule_patience_s)
        self.jobs: Dict[str, JobView] = {}
        self._lock = threading.Lock()
        self._draining: Set[str] = set()
        self._awaiting_resume: Set[str] = set()
        self._log_events: Dict[str, threading.Event] = {}
        from fedml_tpu.telemetry import get_registry

        reg = get_registry()
        self._m_reschedules = reg.counter("sched/reschedules")
        self._m_jobs_lost = reg.counter("sched/jobs_lost")
        self._m_jobs_resumed = reg.counter("sched/jobs_resumed")
        self.subscribe_json(f"sched/{cluster}/master", self._on_message)
        self._watch_started = False

    # -- lifecycle --------------------------------------------------------
    def start(self) -> "MasterAgent":
        if not self._watch_started:
            self._watch_started = True
            self.spawn_loop(self._watch_loop)
        return self

    def shutdown(self) -> None:
        self.stop_agent()

    # -- node registry ----------------------------------------------------
    def live_nodes(self) -> List[str]:
        return self.registry.live()

    def wait_for_nodes(self, n: int, timeout: float = 30.0) -> List[str]:
        return self.registry.wait_for(n, timeout, what="nodes")

    # -- placement helpers -------------------------------------------------
    def _ranks_in_use(self) -> Dict[str, int]:
        in_use: Dict[str, int] = {}
        for view in self.jobs.values():
            for rid, node_id in view.ranks.items():
                if (rid not in view.superseded
                        and view.rank_status[rid] not in RunStatus.TERMINAL):
                    in_use[node_id] = in_use.get(node_id, 0) + 1
        return in_use

    def _hbm_in_use(self) -> Dict[str, float]:
        used: Dict[str, float] = {}
        for view in self.jobs.values():
            if not view.hbm_demand:
                continue
            for rid, node_id in view.ranks.items():
                if (rid not in view.superseded
                        and view.rank_status[rid] not in RunStatus.TERMINAL):
                    used[node_id] = used.get(node_id, 0.0) + view.hbm_demand
        return used

    def _hbm_capacity(self, node_id: str) -> Optional[float]:
        res = self.registry.get(node_id).get("resources") or {}
        limit = res.get("hbm_bytes_limit")
        return float(limit) if limit else None

    def _admits(self, node_id: str, demand: float,
                hbm_used: Dict[str, float]) -> bool:
        """PR 10 peak-HBM admission: a job with a known demand may not
        land on a node advertising a smaller free HBM figure. Unknown
        demand or an un-instrumented node admits (CPU dev clusters)."""
        if demand <= 0:
            return True
        cap = self._hbm_capacity(node_id)
        if cap is None:
            return True
        return cap - hbm_used.get(node_id, 0.0) >= demand

    # -- job control ------------------------------------------------------
    def submit_job(self, spec: JobSpec, n_ranks: int = 1,
                   nodes: Optional[List[str]] = None,
                   extra_env: Optional[Dict[str, Dict[str, str]]] = None,
                   ) -> str:
        """Fan ``spec`` out as ``n_ranks`` runs over the given (or all
        live) nodes, respecting each node's advertised slots and HBM
        headroom. Each rank's process sees FEDML_RANK / FEDML_NUM_RANKS /
        FEDML_JOB_ID; ``extra_env`` maps rank (as str) to additional env
        overrides."""
        with self._lock:
            draining = set(self._draining)
        live = [n for n in self.live_nodes() if n not in draining]
        if nodes:
            missing = sorted(set(nodes) - set(live))
            if missing:
                raise RuntimeError(
                    f"requested nodes not online: {missing} (live: {live})")
        targets = nodes or live
        if not targets:
            raise RuntimeError("no live nodes to schedule on")
        # resource matcher (reference: scheduler_core/scheduler_matcher.py
        # against the GPU inventory): the job yaml's `computing` block
        # filters candidate nodes by their advertised inventory
        req = spec.computing or {}
        min_chips = int(req.get("minimum_num_chips", 0) or 0)
        want_platform = str(req.get("platform", "") or "").lower()
        if min_chips or want_platform:
            matched = []
            for n in targets:
                res = self.registry.get(n).get("resources") or {}
                if min_chips and int(res.get("device_count", 0)) < min_chips:
                    continue
                if (want_platform
                        and str(res.get("platform", "")).lower()
                        != want_platform):
                    continue
                matched.append(n)
            if not matched:
                raise RuntimeError(
                    f"no node satisfies computing requirements {req}; "
                    f"inventories: "
                    f"{ {n: self.registry.get(n).get('resources') for n in targets} }")
            targets = matched
        # expand nodes by their advertised slots (a slot = one rank; each
        # rank is its own JAX/XLA process, so slots bound oversubscription
        # the way the deploy plane's --capacity does), deducting ranks
        # still running from OTHER jobs, interleaved so ranks spread
        # across nodes before doubling up. HBM admission caps each node's
        # usable slots at what its advertised headroom can hold.
        demand = job_hbm_demand(spec)
        with self._lock:
            in_use = self._ranks_in_use()
            hbm_used = self._hbm_in_use()
        remaining = {}
        for n in targets:
            slots = max(0, max(1, int(self.registry.get(n).get("slots", 1)))
                        - in_use.get(n, 0))
            if demand > 0:
                cap = self._hbm_capacity(n)
                if cap is not None:
                    free = cap - hbm_used.get(n, 0.0)
                    slots = min(slots, max(0, int(free // demand)))
            remaining[n] = slots
        slot_list: List[str] = []
        while any(remaining.values()):
            for node_id in targets:
                if remaining[node_id] > 0:
                    remaining[node_id] -= 1
                    slot_list.append(node_id)
        if n_ranks > len(slot_list):
            raise RuntimeError(
                f"job needs {n_ranks} slots, cluster offers {len(slot_list)} "
                f"across {targets}"
                + (f" (peak-HBM admission: {demand:.0f} B/rank)"
                   if demand else ""))
        job_id = uuid.uuid4().hex[:10]
        ranks: Dict[str, str] = {}
        rank_env: Dict[str, Dict[str, str]] = {}
        assignments = []
        for rank in range(n_ranks):
            node_id = slot_list[rank]
            run_id = f"{job_id}-r{rank}"
            ranks[run_id] = node_id
            env = {
                "FEDML_JOB_ID": job_id,
                "FEDML_RANK": str(rank),
                "FEDML_NUM_RANKS": str(n_ranks),
            }
            env.update((extra_env or {}).get(str(rank), {}))
            rank_env[run_id] = env
            assignments.append((node_id, run_id, env))
        view = JobView(job_id, ranks, spec=spec, rank_env=rank_env)
        with self._lock:
            self.jobs[job_id] = view
        for node_id, run_id, env in assignments:
            self._send_start(node_id, run_id, spec, env)
        return job_id

    def _send_start(self, node_id: str, run_id: str, spec: JobSpec,
                    env: Dict[str, str]) -> None:
        self._send(node_id, {"type": "start_run", "run_id": run_id,
                             "spec": spec.wire(), "env": env})

    def stop_job(self, job_id: str) -> bool:
        view = self.jobs.get(job_id)
        if view is None:
            return False
        view.stopped = True
        for run_id, node_id in view.ranks.items():
            if run_id in view.superseded:
                continue
            self._send(node_id, {"type": "stop_run", "run_id": run_id})
        return True

    def job_status(self, job_id: str) -> Optional[Dict]:
        view = self.jobs.get(job_id)
        return view.describe() if view else None

    def wait_job(self, job_id: str, timeout: float = 600.0) -> Dict:
        deadline = time.time() + timeout
        while time.time() < deadline:
            view = self.jobs.get(job_id)
            if view is not None and view.is_terminal:
                return view.describe()
            time.sleep(0.2)
        raise TimeoutError(f"job {job_id} not terminal after {timeout}s")

    def job_logs(self, job_id: str, tail: Optional[int] = 200,
                 timeout: float = 10.0) -> Dict[str, str]:
        """One run view: pull each rank's log from its node."""
        view = self.jobs.get(job_id)
        if view is None:
            return {}
        pending = []
        for run_id, node_id in view.ranks.items():
            event = threading.Event()
            self._log_events[run_id] = event
            pending.append((run_id, event))
            self._send(node_id, {"type": "get_logs", "run_id": run_id,
                                 "tail": tail})
        deadline = time.time() + timeout
        for run_id, event in pending:
            event.wait(timeout=max(0.0, deadline - time.time()))
            self._log_events.pop(run_id, None)
        return dict(view.logs)

    # -- preemption / drain ------------------------------------------------
    def preempt_run(self, run_id: str, grace_s: float = 10.0) -> bool:
        """First-class preempt verb: quiesce ONE run wherever it lives.
        Durable jobs are rescheduled automatically once the node reports
        PREEMPTED."""
        for view in self.jobs.values():
            node_id = view.ranks.get(run_id)
            if node_id is None or run_id in view.superseded:
                continue
            if view.rank_status[run_id] in RunStatus.TERMINAL:
                return False
            self._send(node_id, {"type": "preempt_run", "run_id": run_id,
                                 "grace_s": float(grace_s)})
            return True
        return False

    def drain_node(self, node_id: str, grace_s: float = 10.0,
                   timeout: float = 120.0, reason: str = "drain") -> Dict:
        """Quiesce-and-reschedule everything on a node — the response to
        "this node is being reclaimed in N seconds". Preempts every
        active rank there (SIGTERM + grace via the node agent), waits for
        the quiesce, and lets the PREEMPTED reports drive rescheduling of
        durable jobs onto surviving nodes (non-durable ranks fail: there
        is nothing to resume). The node stays out of placement until
        :meth:`undrain`."""
        with self._lock:
            self._draining.add(node_id)
            victims = [
                (view, rid)
                for view in self.jobs.values()
                for rid, nid in view.ranks.items()
                if nid == node_id and rid not in view.superseded
                and view.rank_status[rid] not in RunStatus.TERMINAL
            ]
        sched_event("node_drain", node=node_id, runs=len(victims),
                    grace_s=grace_s, reason=reason)
        for _, rid in victims:
            self._send(node_id, {"type": "preempt_run", "run_id": rid,
                                 "grace_s": float(grace_s)})
        deadline = time.time() + timeout
        result: Dict = {"node": node_id, "preempted": [], "rescheduled": {},
                        "failed": []}
        for view, rid in victims:
            while time.time() < deadline:
                st = view.rank_status[rid]
                done = st in RunStatus.TERMINAL
                if done and (not view.durable or st != RunStatus.PREEMPTED
                             or rid in view.superseded
                             or (rid in view.resched_refused
                                 and rid not in view.lost_pending)):
                    break  # terminal AND (not resumable / already superseded)
                time.sleep(0.1)
            st = view.rank_status[rid]
            if st == RunStatus.PREEMPTED:
                result["preempted"].append(rid)
                new_rid = view.resched_map.get(rid)
                if new_rid is not None:
                    result["rescheduled"][rid] = new_rid
                elif rid in view.lost_pending:
                    # the watch loop is still retrying within its
                    # patience window — in-flight, not failed
                    result.setdefault("pending", []).append(rid)
                else:
                    # not resumable (or reschedule refused for good):
                    # the rank is lost
                    with self._lock:
                        view.rank_status[rid] = RunStatus.FAILED
                    result["failed"].append(rid)
            elif st not in RunStatus.TERMINAL:
                result["failed"].append(rid)  # never quiesced in time
        return result

    def undrain(self, node_id: str) -> None:
        with self._lock:
            self._draining.discard(node_id)

    def _reschedule(self, view: JobView, old_rid: str, reason: str) -> Optional[str]:
        """Place a successor for a preempted/lost durable rank on a
        surviving node (slot + peak-HBM admission), carrying the original
        env plus FEDML_RESUME=1. Returns the new run_id, or None when no
        node admits the job (the caller fails the rank)."""
        base = old_rid.split(".", 1)[0]
        with self._lock:
            attempts = view.resched_count.get(base, 0)
            if attempts >= self.max_reschedules:
                if old_rid not in view.resched_refused:  # once, not per retry
                    logger.warning(
                        "rank %s: reschedule budget (%d) exhausted",
                        old_rid, self.max_reschedules)
                    sched_event("reschedule_refused", run_id=old_rid,
                                job_id=view.job_id, reason="budget_exhausted",
                                attempts=attempts)
                # refused is terminal for the rank: the job must resolve
                # (JobView.status treats unresumable PREEMPTED as FAILED)
                # instead of reporting RUNNING forever
                view.resched_refused.add(old_rid)
                return None
            old_node = view.ranks[old_rid]
            draining = set(self._draining)
            in_use = self._ranks_in_use()
            hbm_used = self._hbm_in_use()
        candidates = []
        for n in self.live_nodes():
            if n in draining:
                continue
            slots = max(1, int(self.registry.get(n).get("slots", 1)))
            if in_use.get(n, 0) >= slots:
                continue
            if not self._admits(n, view.hbm_demand, hbm_used):
                continue
            candidates.append((n == old_node, in_use.get(n, 0), n))
        if not candidates:
            if old_rid not in view.resched_refused:  # once, not per retry
                logger.warning(
                    "rank %s: no surviving node admits the job "
                    "(demand %.0f B, draining=%s)", old_rid, view.hbm_demand,
                    sorted(draining))
                sched_event("reschedule_refused", run_id=old_rid,
                            job_id=view.job_id, reason=reason,
                            hbm_demand=view.hbm_demand)
            with self._lock:
                view.resched_refused.add(old_rid)
            return None
        candidates.sort()  # prefer other nodes, then least-loaded
        node_id = candidates[0][2]
        new_rid = f"{base}.{attempts + 1}"
        env = dict(view.rank_env.get(old_rid) or {})
        env["FEDML_RESUME"] = "1"
        with self._lock:
            # copy-on-write rebinds, not in-place inserts: describe()/
            # stop_job/wait pollers iterate these containers WITHOUT the
            # lock (they never needed it before this PR made the rank set
            # grow after construction), and a resize mid-iteration raises
            # RuntimeError in the reader
            view.resched_count = {**view.resched_count, base: attempts + 1}
            view.ranks = {**view.ranks, new_rid: node_id}
            view.rank_status = {**view.rank_status,
                                new_rid: RunStatus.QUEUED}
            view.rank_rc = {**view.rank_rc, new_rid: None}
            view.rank_env = {**view.rank_env, new_rid: env}
            view.superseded = view.superseded | {old_rid}
            view.resched_map = {**view.resched_map, old_rid: new_rid}
            self._awaiting_resume.add(new_rid)
        self._m_reschedules.inc()
        sched_event("run_rescheduled", run_id=old_rid, new_run_id=new_rid,
                    job_id=view.job_id, node=node_id, reason=reason)
        self._send_start(node_id, new_rid, view.spec, env)
        return new_rid

    # -- OTA --------------------------------------------------------------
    def push_upgrade(self, package: bytes, version: str,
                     nodes: Optional[List[str]] = None,
                     timeout: float = 60.0) -> Dict[str, str]:
        """Ship a code package to node agents for staged upgrade
        (slave daemon_ota_upgrade parity). Returns node → staged version
        once every target acked (or raises)."""
        if self._store is None:
            from fedml_tpu.core.distributed.communication.object_store import (
                create_object_store,
            )

            self._store = create_object_store()
        targets = nodes or self.live_nodes()
        if not targets:
            raise RuntimeError("no live nodes to upgrade")
        for n in targets:  # clear stale state from any previous push
            self.registry.touch(n, ota_version=None, ota_error=None)
        key = self._store.new_key(f"ota/{version}")
        # returned key is authoritative (CAS backends return a CID)
        key = self._store.put_object(key, package)
        for n in targets:
            self._send(n, {"type": "ota_upgrade", "package_key": key,
                           "version": str(version)})
        deadline = time.time() + timeout
        while time.time() < deadline:
            staged = {n: self.registry.get(n).get("ota_version")
                      for n in targets}
            errors = {n: self.registry.get(n).get("ota_error")
                      for n in targets if self.registry.get(n).get("ota_error")}
            if errors:
                raise RuntimeError(f"OTA staging failed: {errors}")
            if all(v == str(version) for v in staged.values()):
                self._store.delete_object(key)
                return staged
            time.sleep(0.1)
        raise TimeoutError(
            f"OTA {version}: staged on "
            f"{[n for n, v in staged.items() if v == str(version)]} "
            f"of {targets}")

    # -- internals --------------------------------------------------------
    def _send(self, node_id: str, msg: Dict) -> None:
        self.publish_json(f"sched/{self.cluster}/node/{node_id}", msg)

    def _apply_rank_status(self, run_id: str, status: str,
                           returncode=None) -> None:
        for view in self.jobs.values():
            if run_id not in view.rank_status:
                continue
            resumed = False
            needs_resched = False
            # the in-place value writes share the lock with _reschedule's
            # copy-on-write rebinds: an unlocked write racing a rebind
            # could land in the discarded pre-rebind snapshot — the rc
            # would then never heal (one-shot run_status messages are
            # deduped by the node agent; heartbeats carry no rc)
            with self._lock:
                current = view.rank_status[run_id]
                if current not in RunStatus.TERMINAL:
                    view.rank_status[run_id] = status
                    view.rank_rc[run_id] = returncode
                    # a resumed life that ran to its end between two of
                    # the node's status polls is first seen FINISHED
                    if status in (RunStatus.RUNNING, RunStatus.FINISHED) \
                            and run_id in self._awaiting_resume:
                        self._awaiting_resume.discard(run_id)
                        resumed = True
                    needs_resched = (
                        status == RunStatus.PREEMPTED and view.durable
                        and not view.stopped
                        and run_id not in view.superseded)
                elif (current == status and returncode is not None
                      and view.rank_rc[run_id] is None):
                    # heartbeat reconciliation may latch a terminal status
                    # before the one-shot run_status carrying the rc lands;
                    # accept the rc for the SAME status
                    view.rank_rc[run_id] = returncode
                stale_running = (run_id in view.superseded
                                 and status == RunStatus.RUNNING)
            if resumed:
                self._m_jobs_resumed.inc()
                sched_event("run_resumed", run_id=run_id,
                            job_id=view.job_id, node=view.ranks[run_id])
            if needs_resched:
                # quiesce observed (master- OR node-initiated): resume the
                # rank elsewhere — OUTSIDE the lock, _reschedule takes it.
                # A transient refusal (capacity dip) hands off to the
                # watch loop's patience retry — same machinery as a lost
                # rank — rather than permafailing the job, as long as the
                # reschedule budget is not exhausted
                if self._reschedule(view, run_id, "preempted") is None:
                    base = run_id.split(".", 1)[0]
                    if (view.resched_count.get(base, 0)
                            < self.max_reschedules):
                        with self._lock:
                            view.lost_pending.setdefault(run_id,
                                                         time.time())
            if stale_running:
                # a lost node came back still running a run we already
                # rescheduled: exactly one of the twins may live
                logger.warning("superseded run %s reported RUNNING; "
                               "sending stop", run_id)
                self._send(view.ranks[run_id],
                           {"type": "stop_run", "run_id": run_id})
            break

    def _on_message(self, msg: Dict) -> None:
        mtype = msg.get("type")
        nid = str(msg.get("node_id", ""))
        if nid:
            self.liveness.note(nid)
            if self.liveness.is_evicted(nid):
                self.liveness.readmit(nid)
                sched_event("node_readmitted", node=nid)
                # a lost node came back before its ranks were rescheduled:
                # the runs survived with it — cancel the pending loss (the
                # heartbeat reconciles their true statuses)
                with self._lock:
                    views = list(self.jobs.values())
                for view in views:
                    for rid in list(view.lost_pending):
                        if (view.ranks.get(rid) == nid
                                and rid not in view.superseded):
                            with self._lock:
                                view.lost_pending.pop(rid, None)
                            sched_event("run_resurrected", run_id=rid,
                                        job_id=view.job_id, node=nid)
        if mtype == "node_online":
            self.registry.touch(nid, slots=int(msg.get("slots", 1)),
                                resources=msg.get("resources") or {})
        elif mtype == "heartbeat":
            attrs = {}
            if msg.get("slots") is not None:
                attrs["slots"] = int(msg["slots"])
            if msg.get("resources") is not None:
                attrs["resources"] = msg["resources"]
            self.registry.touch(nid, **attrs)
            # reconcile from the heartbeat's run table too: a lost one-shot
            # run_status message must not leave a rank RUNNING forever
            for rid, status in (msg.get("runs") or {}).items():
                self._apply_rank_status(str(rid), str(status))
        elif mtype == "run_status":
            self._apply_rank_status(str(msg["run_id"]), str(msg["status"]),
                                    msg.get("returncode"))
        elif mtype == "ota_staged":
            if msg.get("ok"):
                self.registry.touch(nid, ota_version=str(msg.get("version")),
                                    ota_error=None)
            else:
                self.registry.touch(nid, ota_error=str(msg.get("error")))
        elif mtype == "run_logs":
            rid = str(msg["run_id"])
            for view in self.jobs.values():
                if rid in view.ranks:
                    view.logs[rid] = str(msg.get("data", ""))
                    break
            event = self._log_events.get(rid)
            if event is not None:
                event.set()

    def _watch_loop(self) -> None:
        """Dead-node handling, two deadlines: a node dark past the
        heartbeat timeout takes its non-durable ranks to FAILED (the
        reference master's edge-offline handling); a node silent past
        ``node_loss_deadline_s`` has its durable ranks declared LOST and
        rescheduled onto surviving nodes, where they resume from their
        last durable state."""
        while not self._stopping.is_set():
            dark = set(self.registry.dark())
            with self._lock:
                views = list(self.jobs.values())
            for view in views:
                if view.durable:
                    continue  # durable jobs wait for the loss deadline
                for rid, node_id in view.ranks.items():
                    if (node_id in dark and rid not in view.superseded
                            and view.rank_status[rid] not in RunStatus.TERMINAL):
                        logger.warning("job %s rank %s lost: node %s dark",
                                       view.job_id, rid, node_id)
                        with self._lock:
                            view.rank_status[rid] = RunStatus.FAILED
            for node_id in self.liveness.silent_peers():
                if self.liveness.evict(node_id):
                    sched_event("node_lost", node=node_id,
                                deadline_s=self.node_loss_deadline_s)
            evicted = set(self.liveness.evicted())
            now = time.time()
            for view in views:
                if not view.durable or view.stopped:
                    continue
                for rid, nid in list(view.ranks.items()):
                    if rid in view.superseded:
                        continue
                    pending_since = view.lost_pending.get(rid)
                    if pending_since is None:
                        if (nid not in evicted
                                or view.rank_status[rid] in RunStatus.TERMINAL):
                            continue
                        # first sighting: declare the rank lost
                        with self._lock:
                            view.lost_pending[rid] = now
                        pending_since = now
                        self._m_jobs_lost.inc()
                        sched_event("job_lost", run_id=rid,
                                    job_id=view.job_id, node=nid)
                        logger.warning(
                            "job %s rank %s LOST with node %s (silent > "
                            "%gs); rescheduling", view.job_id, rid, nid,
                            self.node_loss_deadline_s)
                        # tell the node to stop the zombie if it ever
                        # returns, then place the successor
                        self._send(nid, {"type": "stop_run", "run_id": rid})
                    if self._reschedule(view, rid, "retry") is not None:
                        with self._lock:
                            view.lost_pending.pop(rid, None)
                            if view.rank_status[rid] == RunStatus.RUNNING:
                                # lost-node rank: the row will never
                                # report again — close it out (a preempt-
                                # pending rank keeps its honest PREEMPTED)
                                view.rank_status[rid] = RunStatus.FAILED
                    elif now - pending_since > self.reschedule_patience_s:
                        # patience exhausted: the rank fails for real
                        with self._lock:
                            view.lost_pending.pop(rid, None)
                            view.rank_status[rid] = RunStatus.FAILED
                        sched_event("reschedule_abandoned", run_id=rid,
                                    job_id=view.job_id,
                                    patience_s=self.reschedule_patience_s)
                    # else: no admissible node RIGHT NOW — retry next sweep
            time.sleep(0.5)
