"""Fleet scheduler — N concurrent training jobs on ONE serverless pool.

MLLess's thesis is cost efficiency from sub-second billing, but a single
job pays for every barrier stall: an ISP worker blocked on its slowest
peer is a live, billed function doing nothing.  The fleet scheduler
(DESIGN.md §14) admits N jobs onto one shared broker/worker pool so one
job's stall absorbs another job's compute inside the same 100 ms billing
quantum — the adaptive multi-job gap SMLT frames (PAPERS.md).

Architecture — every layer keeps its single-job semantics per job:

* **brokers**: each shard process hosts one independent ``BrokerCore``
  per job (``broker.Broker`` with a ``{"jobs": ...}`` config).  Requests
  route by their ``job`` header; all cores share one TCP port, one WAL
  (records are job-stamped and replay back into the right core) and the
  shm segments.  A shard SIGKILL replays every job's history at once.
* **keys**: every leaf key is prefixed ``j<id>/`` through
  ``sharding.job_namespace``.  The prefix is uniform within a job, so
  the (size desc, key asc) partition — and hence each job's per-shard
  slices, byte accounting and float summation order — is IDENTICAL to
  the same job run solo.  Concurrency is observationally invisible:
  final params are bit-identical to the solo run (the repo's standard
  gate, asserted across {tcp,shm} x {1,2} brokers x {isp,ssp}).
* **workers**: one invocation process per slot runs one training thread
  per admitted job (``worker.run_worker_fleet``) — bin-packing.  A
  process-wide compute lock models the 1-vCPU function: a job computes
  exactly while its siblings wait on barriers.  The first thread to hit
  its invocation budget declares a process-wide boundary; siblings wind
  down as ``bye:invocation-end`` within one 2 s barrier slice and the
  scheduler respawns ONE invocation for all of them.
* **scale-in**: one *independent, unmodified* ``ScaleInAutoTuner`` per
  job, fed that job's own telemetry — each job walks its own knee curve.
  The scheduler arbitrates a shared ``pool_budget``: when the fleet's
  active (worker, job) pairs exceed it, the job holding the most active
  workers gives one up (reason ``fair-share``).
* **billing**: the pool pays ONE bill (quantum-rounded invocation
  lifetimes + the shared VMs billed once on the fleet wall clock);
  ``core.billing.multi_job_rollup`` attributes it to jobs proportionally
  by measured busy seconds.  The headline claim — two bin-packed jobs
  cost less than the same two jobs solo — is measured live by
  ``benchmarks/fig11_multijob.py``.

``launch/train.py --jobs jobs.json`` is the CLI entry point.
"""

from __future__ import annotations

import dataclasses
import json
import os
import signal
import subprocess
import sys
import time
from typing import Any, Optional

from repro.core.autotuner import (
    AutoTunerConfig,
    ScaleInAutoTuner,
    TopologyTuner,
    TopologyTunerConfig,
)
from repro.core.billing import CommModel, faas_cost, multi_job_rollup
from repro.runtime import protocol
from repro.runtime import workload as workload_lib
from repro.runtime.sharding import job_namespace
from repro.runtime.supervisor import FaaSJobConfig


@dataclasses.dataclass
class FleetConfig:
    """N admitted jobs sharing one broker/worker pool.

    Jobs must agree on the pool topology (``n_brokers``, ``transport``) —
    they share the processes.  Everything else (workload, wire scheme,
    consistency, slack, step budgets, tuners, fault hooks) is per job.
    Each job's ``run_dir`` is forced to ``<run_dir>/jobs/<job_id>`` so
    checkpoints and JIT caches never collide.
    """

    run_dir: str
    jobs: dict[str, FaaSJobConfig] = dataclasses.field(default_factory=dict)
    # fair-share arbitration: max concurrent active (worker, job) pairs
    # across the fleet; None = uncapped (each job keeps its own pool)
    pool_budget: Optional[int] = None
    poll_interval_s: float = 0.05
    deadline_s: float = 600.0


@dataclasses.dataclass
class _FleetSlot:
    """One invocation slot (one billable process hosting >= 1 job)."""

    worker: int
    proc: Optional[subprocess.Popen] = None
    spawned_at: float = 0.0
    invocations: int = 0
    # per-job shm segment names of the live invocation
    shm_segs: list = dataclasses.field(default_factory=list)

    @property
    def alive(self) -> bool:
        return self.proc is not None and self.proc.poll() is None


@dataclasses.dataclass
class _BrokerShard:
    shard: int
    proc: Optional[subprocess.Popen] = None
    addr: Optional[tuple[str, int]] = None
    spawns: int = 0

    @property
    def alive(self) -> bool:
        return self.proc is not None and self.proc.poll() is None


@dataclasses.dataclass
class _JobState:
    """Per-job control-plane state (the solo supervisor's fields, keyed)."""

    cfg: FaaSJobConfig
    wl: Any
    history: list = dataclasses.field(default_factory=list)
    poll_since: int = 1
    frontier: int = 0
    evictions: dict = dataclasses.field(default_factory=dict)
    statuses: dict = dataclasses.field(default_factory=dict)
    scale_events: list = dataclasses.field(default_factory=list)
    scripted_fired: int = 0
    killed_once: bool = False
    broker_killed_once: bool = False
    tuner: Optional[ScaleInAutoTuner] = None
    # observe-only topology tuner (cfg.topology_tune under a fleet): the
    # broker pool is SHARED, so no job may re-shard it live — the tuner
    # measures the running cell and the result carries a model-ranked
    # recommendation instead of a handover (DESIGN.md §16)
    topo_tuner: Optional[TopologyTuner] = None
    # (worker -> 'done' | 'evicted'): this job's terminal workers
    terminal: dict = dataclasses.field(default_factory=dict)

    def live_workers(self) -> list[int]:
        return [
            w for w in range(self.cfg.n_workers) if w not in self.terminal
        ]

    def active_workers(self) -> list[int]:
        """Live and not yet scheduled to leave."""
        return [w for w in self.live_workers() if w not in self.evictions]

    @property
    def complete(self) -> bool:
        return not self.live_workers()


class FleetScheduler:
    """Admission + packing + fair-share control plane over one pool."""

    def __init__(self, fleet: FleetConfig):
        if not fleet.jobs:
            raise ValueError("fleet needs at least one job")
        self.fleet = fleet
        self.job_ids = sorted(fleet.jobs)
        for jid in self.job_ids:
            job_namespace(jid)  # validates the id charset
        cfgs = [fleet.jobs[j] for j in self.job_ids]
        if len({c.n_brokers for c in cfgs}) != 1:
            raise ValueError("fleet jobs must agree on n_brokers")
        if len({c.transport for c in cfgs}) != 1:
            raise ValueError("fleet jobs must agree on transport")
        for jid, c in zip(self.job_ids, cfgs):
            if c.transport not in ("tcp", "shm"):
                raise ValueError(f"job {jid}: bad transport {c.transport!r}")
            if c.consistency not in ("isp", "ssp"):
                raise ValueError(
                    f"job {jid}: bad consistency {c.consistency!r}"
                )
            if c.consistency == "ssp" and c.slack < 0:
                raise ValueError(f"job {jid}: slack must be >= 0")
            if c.prewarm:
                # pre-warmed respawn is a solo-supervisor feature; a fleet
                # slot already overlaps init across jobs by construction
                raise ValueError(
                    f"job {jid}: prewarm is not supported under the fleet "
                    "scheduler (use the solo supervisor)"
                )
            if c.scripted_retunes:
                # the broker pool is shared across jobs: one job forcing a
                # re-shard would fence every other job's workers mid-step
                raise ValueError(
                    f"job {jid}: scripted_retunes is not supported under "
                    "the fleet scheduler (use the solo supervisor)"
                )
            if c.chaos is not None:
                # a chaos plan SIGKILLs shared slots/brokers — the blast
                # radius crosses tenant boundaries; the legacy per-job
                # kill_*_at_step knobs above remain the fleet's fault hooks
                raise ValueError(
                    f"job {jid}: chaos plans are not supported under the "
                    "fleet scheduler (use the solo supervisor)"
                )
        self.n_brokers = cfgs[0].n_brokers
        self.transport = cfgs[0].transport
        # admission: pin each job's run_dir inside the fleet's
        self.jobs: dict[str, _JobState] = {}
        for jid in self.job_ids:
            cfg = dataclasses.replace(
                fleet.jobs[jid],
                run_dir=os.path.join(fleet.run_dir, "jobs", jid),
            )
            st = _JobState(cfg=cfg, wl=workload_lib.build(
                cfg.workload, cfg.workload_cfg
            ))
            if cfg.autotune:
                st.tuner = ScaleInAutoTuner(
                    cfg.tuner or AutoTunerConfig(), cfg.n_workers
                )
            if cfg.topology_tune:
                # observe-only: single cell = the fleet's shared topology
                st.topo_tuner = TopologyTuner(
                    [{
                        "n_brokers": self.n_brokers,
                        "transport": self.transport,
                        "wire_scheme": cfg.wire_scheme,
                        "shard_split_bytes": cfg.shard_split_bytes,
                    }],
                    TopologyTunerConfig(),
                    comm=CommModel(),
                    n_workers=cfg.n_workers,
                )
            self.jobs[jid] = st
        n_slots = max(c.n_workers for c in cfgs)
        self.slots = [_FleetSlot(worker=w) for w in range(n_slots)]
        self.shards = [_BrokerShard(shard=s) for s in range(self.n_brokers)]
        self._conns: list[Optional[protocol.Connection]] = (
            [None] * self.n_brokers
        )
        self.lifetimes: list[float] = []
        self.respawns: list[dict] = []
        self.broker_respawns: list[dict] = []
        self._stopping = False
        import secrets

        self._shm_token = f"fl{os.getpid():x}{secrets.token_hex(2)}"
        self._shm_segments: dict[str, Any] = {}

    # -- job placement ---------------------------------------------------------

    def _hosted_jobs(self, slot: _FleetSlot) -> list[str]:
        """Jobs this slot still runs: admitted there and not terminal."""
        return [
            jid for jid in self.job_ids
            if slot.worker < self.jobs[jid].cfg.n_workers
            and slot.worker not in self.jobs[jid].terminal
        ]

    # -- env / broker lifecycle (the solo supervisor's recipe, fleet dirs) -----

    def _base_env(self) -> dict:
        import repro

        pkg_dir = (
            os.path.dirname(repro.__file__)
            if getattr(repro, "__file__", None)
            else next(iter(repro.__path__))
        )
        src = os.path.dirname(os.path.abspath(pkg_dir))
        env = dict(os.environ)
        env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
        # workers and brokers are host processes: on a machine with a chip
        # they must never reach for it (a chip belongs to one process)
        env["JAX_PLATFORMS"] = "cpu"
        return env

    def _worker_env(self) -> dict:
        env = self._base_env()
        env.setdefault("XLA_FLAGS", "--xla_cpu_multi_thread_eigen=false "
                       "intra_op_parallelism_threads=1")
        env.setdefault("OMP_NUM_THREADS", "1")
        env.setdefault("OPENBLAS_NUM_THREADS", "1")
        return env

    def _broker_dir(self) -> str:
        return os.path.join(self.fleet.run_dir, "broker")

    def _spawn_broker(self, bs: _BrokerShard) -> None:
        bdir = self._broker_dir()
        os.makedirs(bdir, exist_ok=True)
        logdir = os.path.join(self.fleet.run_dir, "logs")
        os.makedirs(logdir, exist_ok=True)
        port_file = os.path.join(bdir, f"shard{bs.shard:02d}.port")
        if os.path.exists(port_file):
            os.unlink(port_file)
        wal_path = os.path.join(bdir, f"shard{bs.shard:02d}.wal")
        if bs.spawns == 0 and os.path.exists(wal_path):
            os.unlink(wal_path)  # fresh fleet: never replay a previous one
        log = open(
            os.path.join(
                logdir, f"broker{bs.shard:02d}.spawn{bs.spawns:02d}.log"
            ),
            "wb",
        )
        bs.proc = subprocess.Popen(
            [
                sys.executable, "-m", "repro.runtime.broker",
                "--config", os.path.join(bdir, "fleet.json"),
                "--shard-id", str(bs.shard),
                "--n-shards", str(self.n_brokers),
                "--port", str(bs.addr[1] if bs.addr else 0),
                "--wal", wal_path,
                "--port-file", port_file,
            ],
            stdout=log,
            stderr=subprocess.STDOUT,
            env=self._base_env(),
        )
        log.close()
        bs.spawns += 1
        deadline = time.monotonic() + max(
            self.jobs[j].cfg.broker_spawn_timeout_s for j in self.job_ids
        )
        while not os.path.exists(port_file):
            if bs.proc.poll() is not None:
                raise RuntimeError(
                    f"fleet broker shard {bs.shard} exited during spawn "
                    f"(code {bs.proc.returncode}); logs in {logdir}"
                )
            if time.monotonic() > deadline:
                raise RuntimeError(
                    f"fleet broker shard {bs.shard} did not listen in time"
                )
            time.sleep(0.01)
        with open(port_file) as f:
            host, port = f.read().strip().rsplit(":", 1)
        bs.addr = (host, int(port))

    def _start_brokers(self) -> None:
        bdir = self._broker_dir()
        os.makedirs(bdir, exist_ok=True)
        cfg_doc = {
            "jobs": {
                jid: self.jobs[jid].cfg.job_dict(self.jobs[jid].wl.n_batches)
                for jid in self.job_ids
            }
        }
        with open(os.path.join(bdir, "fleet.json"), "w") as f:
            json.dump(cfg_doc, f, indent=1)
        for bs in self.shards:
            self._spawn_broker(bs)

    def _reap_brokers(self) -> None:
        if self._stopping:
            return
        for bs in self.shards:
            if bs.proc is not None and bs.proc.poll() is not None:
                self.broker_respawns.append(
                    {
                        "shard": bs.shard,
                        "exit_code": bs.proc.returncode,
                        "at_frontier": {
                            j: self.jobs[j].frontier for j in self.job_ids
                        },
                    }
                )
                if self._conns[bs.shard] is not None:
                    self._conns[bs.shard].close()
                    self._conns[bs.shard] = None
                self._spawn_broker(bs)
                if self.transport == "shm":
                    self._reserve_shard_shm(bs)

    # -- shm lifecycle (per (slot, job, shard) segment families) ---------------

    def _teardown_slot_shm(self, slot: _FleetSlot) -> None:
        from repro.wire import shm

        for name in slot.shm_segs:
            seg = self._shm_segments.pop(name, None)
            if seg is not None:
                seg.unlink()
            else:  # pragma: no cover - belt and braces
                shm.Segment.unlink_by_name(name)
        slot.shm_segs = []

    def _setup_slot_shm(self, slot: _FleetSlot, jids: list[str]) -> str:
        """Fresh per-job segment families for this slot's next invocation;
        the worker's job thread for ``jid`` attaches
        ``<base>g<jid>s<shard>`` (worker.run_worker_fleet)."""
        from repro.wire import shm

        self._teardown_slot_shm(slot)
        base = f"{self._shm_token}w{slot.worker}i{slot.invocations}"
        ring = max(self.jobs[j].cfg.shm_ring_bytes for j in jids)
        names = [
            f"{base}g{jid}s{s}"
            for jid in jids for s in range(self.n_brokers)
        ]
        for name in names:
            self._shm_segments[name] = shm.Segment.create(
                name, ring_bytes=ring
            )
        for jid in jids:
            for s in range(self.n_brokers):
                resp, _ = self._rpc(
                    {"t": "shm_serve", "seg": f"{base}g{jid}s{s}"}, shard=s
                )
                if not resp.get("ok"):  # pragma: no cover - defensive
                    raise RuntimeError(f"shard {s} refused shm_serve: {resp}")
        slot.shm_segs = names
        return base

    def _reserve_shard_shm(self, bs: _BrokerShard) -> None:
        for slot in self.slots:
            if not slot.shm_segs:
                continue
            for name in slot.shm_segs:
                if not name.endswith(f"s{bs.shard}"):
                    continue
                for attempt in range(3):
                    try:
                        protocol.request(
                            bs.addr, {"t": "shm_serve", "seg": name},
                            timeout=10.0,
                        )
                        break
                    except (ConnectionError, OSError, TimeoutError):
                        if attempt == 2:
                            break
                        time.sleep(0.2)

    # -- worker lifecycle ------------------------------------------------------

    def _spawn(self, slot: _FleetSlot) -> None:
        jids = self._hosted_jobs(slot)
        assert jids, "spawning a slot with no live jobs"
        logdir = os.path.join(self.fleet.run_dir, "logs")
        os.makedirs(logdir, exist_ok=True)
        log = open(
            os.path.join(
                logdir, f"w{slot.worker:03d}.inv{slot.invocations:03d}.log"
            ),
            "wb",
        )
        brokers = ",".join(f"{h}:{p}" for h, p in
                           (bs.addr for bs in self.shards))
        cmd = [
            sys.executable, "-m", "repro.runtime.worker",
            "--brokers", brokers,
            "--worker-id", str(slot.worker),
            "--jobs", ",".join(jids),
        ]
        if self.transport == "shm":
            cmd += ["--transport", "shm",
                    "--shm-seg", self._setup_slot_shm(slot, jids)]
        slot.proc = subprocess.Popen(
            cmd,
            stdout=log,
            stderr=subprocess.STDOUT,
            env=self._worker_env(),
        )
        log.close()
        slot.spawned_at = time.monotonic()
        slot.invocations += 1

    def _reap(self, slot: _FleetSlot) -> None:
        """Classify an exited invocation per hosted job and respawn while
        any of them lives on.  Terminal statuses (done/evicted) were
        already folded in from live polls; what's left per job is either
        a clean invocation boundary or a crash (replay)."""
        assert slot.proc is not None
        code = slot.proc.returncode
        self.lifetimes.append(time.monotonic() - slot.spawned_at)
        slot.proc = None
        live = []
        for jid in self._hosted_jobs(slot):
            st = self.jobs[jid]
            status = st.statuses.get(str(slot.worker), "")
            if status == "bye:invocation-end":
                live.append(jid)
            else:
                # no goodbye for this job: crash — replay from its newest
                # checkpoint (per-job ckpt dirs, per-job WAL'd history)
                from repro.checkpoint import store as ckpt

                restored = ckpt.latest_step(
                    os.path.join(
                        st.cfg.run_dir, "ckpt", f"w{slot.worker:03d}"
                    )
                )
                self.respawns.append(
                    {
                        "worker": slot.worker,
                        "job": jid,
                        "exit_code": code,
                        "restored_step": restored or 0,
                        "at_frontier": st.frontier,
                    }
                )
                live.append(jid)
        if live:
            self._spawn(slot)
        else:
            self._teardown_slot_shm(slot)

    def _fold_statuses(self) -> None:
        """Terminal per-(worker, job) transitions arrive through live
        polls — a thread saying ``bye:done``/``bye:evicted`` ends that
        job on that slot while the PROCESS may keep running siblings."""
        for jid in self.job_ids:
            st = self.jobs[jid]
            for w_str, status in st.statuses.items():
                w = int(w_str)
                if w in st.terminal:
                    continue
                if status == "bye:done":
                    st.terminal[w] = "done"
                elif status == "bye:evicted":
                    st.terminal[w] = "evicted"

    # -- control-plane RPC -----------------------------------------------------

    def _rpc(
        self, header: dict, payload: bytes = b"", shard: int = 0,
        tries: int = 8,
    ) -> tuple[dict, bytes]:
        last: Optional[Exception] = None
        for i in range(tries):
            if self._conns[shard] is None:
                self._conns[shard] = protocol.Connection(
                    self.shards[shard].addr, timeout=30.0
                )
            try:
                return self._conns[shard].request(header, payload)
            except (ConnectionError, OSError, TimeoutError) as e:
                last = e
                self._conns[shard].close()
                self._conns[shard] = None
                self._reap_brokers()
                time.sleep(0.1 * (i + 1))
        assert last is not None
        raise last

    def _poll_job(self, jid: str) -> None:
        st = self.jobs[jid]
        resp, _ = self._rpc(
            {"t": "poll", "since": st.poll_since, "job": jid}
        )
        for row in resp["rows"]:
            st.history.append(row)
            st.poll_since = row["step"] + 1
            st.frontier = max(st.frontier, row["step"])
            if st.tuner is not None:
                st.tuner.observe(row["step"], row["loss"], row["dur_s"])
            if st.topo_tuner is not None:
                st.topo_tuner.observe(row["dur_s"], row.get("phase"))
        st.evictions = {int(k): v for k, v in resp["evictions"].items()}
        st.statuses = resp["statuses"]

    def _evict_victim(self, jid: str, reason: str, s_delta=None) -> bool:
        """One worker leaves job ``jid`` (its thread flushes and exits;
        the slot keeps running its other jobs)."""
        st = self.jobs[jid]
        victims = st.active_workers()
        if len(victims) <= 1:
            return False
        victim = max(victims)
        resp, _ = self._rpc({"t": "evict", "worker": victim, "job": jid})
        if not resp.get("granted"):
            return False
        for s in range(1, self.n_brokers):
            self._rpc(
                {"t": "evict_apply", "worker": victim,
                 "step": resp["evict_step"], "job": jid},
                shard=s,
            )
        st.evictions[victim] = resp["evict_step"]
        st.scale_events.append(
            {
                "worker": victim,
                "evict_step": resp["evict_step"],
                "at_frontier": st.frontier,
                "s_delta": s_delta,
                "reason": reason,
            }
        )
        return True

    def _fair_share(self) -> None:
        """Arbitrate the shared pool: while the fleet holds more active
        (worker, job) pairs than the budget, the job with the most active
        workers gives one up — each job still walks its own knee curve,
        the budget only caps the sum."""
        budget = self.fleet.pool_budget
        if budget is None:
            return
        for _ in range(len(self.job_ids) * max(len(self.slots), 1)):
            counts = {
                jid: len(self.jobs[jid].active_workers())
                for jid in self.job_ids
                if not self.jobs[jid].complete
            }
            if sum(counts.values()) <= budget:
                return
            for jid in sorted(counts, key=lambda j: (-counts[j], j)):
                if self._evict_victim(jid, "fair-share"):
                    break
            else:
                return  # nobody can shrink further

    # -- main loop -------------------------------------------------------------

    def run(self) -> dict:
        fleet = self.fleet
        os.makedirs(fleet.run_dir, exist_ok=True)
        for jid in self.job_ids:
            os.makedirs(self.jobs[jid].cfg.run_dir, exist_ok=True)
        t0 = time.monotonic()
        shard_stats: dict[str, list] = {jid: [] for jid in self.job_ids}
        try:
            self._start_brokers()
            for slot in self.slots:
                self._spawn(slot)
            deadline = t0 + fleet.deadline_s
            while True:
                time.sleep(fleet.poll_interval_s)
                self._reap_brokers()
                for jid in self.job_ids:
                    self._poll_job(jid)
                self._fold_statuses()

                # per-job fault hooks: a worker SIGKILL hits the PROCESS
                # (all jobs on that slot replay — the honest fleet fault)
                for jid in self.job_ids:
                    st = self.jobs[jid]
                    if (
                        st.cfg.kill_worker_at_step is not None
                        and not st.killed_once
                    ):
                        w, at = st.cfg.kill_worker_at_step
                        slot = self.slots[w]
                        if st.frontier >= at and slot.alive:
                            slot.proc.send_signal(signal.SIGKILL)
                            st.killed_once = True
                    if (
                        st.cfg.kill_broker_at_step is not None
                        and not st.broker_killed_once
                    ):
                        s, at = st.cfg.kill_broker_at_step
                        bs = self.shards[s]
                        if st.frontier >= at and bs.alive:
                            bs.proc.send_signal(signal.SIGKILL)
                            st.broker_killed_once = True

                for slot in self.slots:
                    if slot.proc is not None and slot.proc.poll() is not None:
                        # refresh per-job statuses so just-sent byes are
                        # not misread as crashes
                        for jid in self._hosted_jobs(slot):
                            self._poll_job(jid)
                        self._fold_statuses()
                        self._reap(slot)

                all_alive = all(
                    slot.alive
                    for slot in self.slots if self._hosted_jobs(slot)
                )
                if all_alive:
                    for jid in self.job_ids:
                        st = self.jobs[jid]
                        if st.scripted_fired < len(
                            st.cfg.scripted_evict_steps
                        ):
                            nxt = st.cfg.scripted_evict_steps[
                                st.scripted_fired
                            ]
                            if st.frontier >= nxt:
                                if self._evict_victim(jid, "scripted"):
                                    st.scripted_fired += 1
                        if st.tuner is not None and st.history:
                            decision = st.tuner.decide()
                            if decision.remove_worker:
                                self._evict_victim(
                                    jid, decision.reason, decision.s_delta
                                )
                    self._fair_share()

                if all(self.jobs[j].complete for j in self.job_ids):
                    for jid in self.job_ids:
                        self._poll_job(jid)
                    break
                if time.monotonic() > deadline:
                    status_dump = {
                        j: self.jobs[j].statuses for j in self.job_ids
                    }
                    raise RuntimeError(
                        f"fleet deadline ({fleet.deadline_s}s) exceeded; "
                        f"frontiers="
                        f"{ {j: self.jobs[j].frontier for j in self.job_ids} }"
                        f"; statuses={status_dump}; logs in "
                        f"{os.path.join(fleet.run_dir, 'logs')}"
                    )

            # drain: every job is complete, so each slot's process is
            # exiting on its own — wait for it and bill its real lifetime
            # (terminal transitions fold in from live polls, so the loop
            # breaks BEFORE the procs finish exiting)
            for slot in self.slots:
                if slot.proc is not None:
                    try:
                        slot.proc.wait(timeout=30.0)
                    except subprocess.TimeoutExpired:
                        slot.proc.kill()
                        slot.proc.wait()
                    self.lifetimes.append(
                        time.monotonic() - slot.spawned_at
                    )
                    slot.proc = None
                    self._teardown_slot_shm(slot)

            self._stopping = True
            # one shutdown per (job core, shard); the shard process exits
            # after its LAST core is down, so order jobs inner
            for s in range(self.n_brokers):
                for jid in self.job_ids:
                    resp, _ = self._rpc({"t": "shutdown", "job": jid},
                                        shard=s)
                    shard_stats[jid].append(resp)
        finally:
            for slot in self.slots:
                if slot.alive:
                    slot.proc.kill()
            for conn in self._conns:
                if conn is not None:
                    conn.close()
            self._conns = [None] * self.n_brokers
            for bs in self.shards:
                if bs.proc is not None:
                    bs.proc.terminate()
                    try:
                        bs.proc.wait(timeout=5.0)
                    except subprocess.TimeoutExpired:
                        bs.proc.kill()
            for seg in self._shm_segments.values():
                seg.unlink()
            self._shm_segments.clear()

        wall = time.monotonic() - t0
        return self._result(wall, shard_stats)

    # -- results ---------------------------------------------------------------

    def _job_result(self, jid: str, stats_rows: list) -> dict:
        st = self.jobs[jid]
        hist = st.history
        durs = [r["dur_s"] for r in hist if r.get("dur_s")]
        stats: dict[str, dict[str, int]] = {}
        for resp in stats_rows:
            for kind, row in (resp.get("stats") or {}).items():
                agg = stats.setdefault(
                    kind, {"count": 0, "bytes_in": 0, "bytes_out": 0}
                )
                for k in agg:
                    agg[k] += row.get(k, 0)
        busy_s = sum(
            float(r["dur_s"]) * int(r.get("p_active", 1)) for r in hist
            if r.get("dur_s")
        )
        return {
            "job_id": jid,
            "workload": st.wl.name,
            "run_dir": st.cfg.run_dir,
            "n_workers": st.cfg.n_workers,
            "steps": st.frontier,
            "final_loss": hist[-1]["loss"] if hist else None,
            "final_pool": sum(
                1 for v in st.terminal.values() if v == "done"
            ),
            "history": hist,
            "measured_step_s": (sum(durs) / len(durs)) if durs else None,
            "busy_s": busy_s,
            "wire_bytes_total": sum(r["wire_bytes"] for r in hist),
            "invariant_max_err": max(
                (r["inv_err"] for r in hist), default=0.0
            ),
            "scale_events": st.scale_events,
            "evictions": dict(st.evictions),
            "dup_mismatches": sum(
                int(r.get("dup_mismatches", 0)) for r in stats_rows
            ),
            "broker_stats": stats,
            "broker_stats_per_shard": [
                r.get("stats") or {} for r in stats_rows
            ],
            "broker_update_bytes_per_shard": [
                int(r.get("update_bytes", 0)) for r in stats_rows
            ],
            "topology_recommendation": self._topo_recommendation(jid),
        }

    def _topo_recommendation(self, jid: str) -> Optional[dict]:
        """Observe-only topology advice for one fleet job: the shared pool
        is never re-sharded live, so we measure the running cell and rank
        the neighbouring cells with the cost model instead."""
        st = self.jobs[jid]
        if st.topo_tuner is None:
            return None
        hist = st.history
        steps = max(len(hist), 1)
        bytes_per_step = (
            sum(float(r.get("wire_bytes") or 0.0) for r in hist) / steps
        )
        p = st.cfg.n_workers
        current = dict(st.topo_tuner.cells[0])
        candidates = [current]
        flip_b = dict(current)
        flip_b["n_brokers"] = 2 if int(current["n_brokers"]) == 1 else 1
        candidates.append(flip_b)
        flip_t = dict(current)
        flip_t["transport"] = (
            "shm" if current["transport"] == "tcp" else "tcp"
        )
        candidates.append(flip_t)
        comm = CommModel()
        ranked = sorted(
            (
                {
                    "cell": c,
                    "model_exchange_s": comm.indirect_exchange_time(
                        bytes_per_step, p, n_redis=int(c["n_brokers"])
                    ),
                }
                for c in candidates
            ),
            key=lambda r: r["model_exchange_s"],
        )
        return {
            "mode": "observe-only",
            "note": "fleet pool is shared; no live re-shard per job",
            "measured": st.topo_tuner.cell_stats(0),
            "model_ranked_cells": ranked,
        }

    def _result(self, wall: float, shard_stats: dict[str, list]) -> dict:
        per_job = {
            jid: self._job_result(jid, shard_stats[jid])
            for jid in self.job_ids
        }
        bill = faas_cost(self.lifetimes, wall, n_redis=self.n_brokers)
        rollup = multi_job_rollup(
            self.lifetimes, wall, self.n_brokers,
            {jid: per_job[jid]["busy_s"] for jid in self.job_ids},
        )
        return {
            "jobs": per_job,
            "job_ids": list(self.job_ids),
            "n_brokers": self.n_brokers,
            "transport": self.transport,
            "pool_budget": self.fleet.pool_budget,
            "wall_s": wall,
            "n_invocations": len(self.lifetimes),
            "lifetimes_s": list(self.lifetimes),
            "respawns": self.respawns,
            "n_respawns": len(self.respawns),
            "broker_respawns": self.broker_respawns,
            "dup_mismatches": sum(
                per_job[j]["dup_mismatches"] for j in self.job_ids
            ),
            "bill": {
                "worker_seconds": bill.worker_seconds,
                "wall_seconds": bill.wall_seconds,
                "worker_cost": bill.worker_cost,
                "infra_cost": bill.infra_cost,
                "n_redis": bill.n_redis,
                "total": bill.total,
            },
            "rollup": {
                "per_job": rollup["per_job"],
                "total": rollup["bill"].total,
            },
        }


def run_fleet(fleet: FleetConfig) -> dict:
    """Run N admitted jobs to completion on one pool; returns the fleet
    result dict (``jobs[<id>]`` mirrors the solo supervisor's results)."""
    return FleetScheduler(fleet).run()
