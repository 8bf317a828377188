r"""Host-side supervisor of the FaaS runtime — the MLLess scheduler (§4.2, §5).

Owns one training job end to end:

* spawns ``n_brokers`` update-broker shard processes (``runtime.broker``;
  the sharded Redis role, shard 0 doubling as the coordinator/messaging
  VM) and ``n_workers`` real OS worker processes (``runtime.worker``),
  each invocation-bounded;
* polls live (loss, step-duration) telemetry off the coordinator and feeds
  the *unmodified* ``core.autotuner.ScaleInAutoTuner`` — scale-in decisions
  are made from measured wall-clock, not modelled time;
* on a decision, evicts the highest-id worker: the coordinator picks the
  effective step (then the supervisor installs it on the other shards via
  ``evict_apply``), the worker flushes its replica through the
  mean-preserving reintegration path (``dist.elastic.reintegrate_into``)
  and exits, and the process's real lifetime stops being billed;
* respawns workers at invocation boundaries and after crashes — a crashed
  worker restores the newest ``checkpoint.store`` snapshot and replays
  forward deterministically (the brokers' update log serves the history);
* respawns a crashed *broker shard* on its original port — the shard
  replays its write-ahead log before binding, so workers' idempotent RPC
  retries land on bit-identical state (``dup_mismatches`` stays 0);
* meters every invocation's measured lifetime through
  ``core.billing.faas_cost`` at the 100 ms quantum with
  ``n_redis == n_brokers``, so a live run emits a real ``FaaSBill`` whose
  infra cost matches the topology it actually ran.

State machine per worker slot::

    spawned -> running -> { done | evicted }          (terminal)
                      \-> invocation-end -> respawn -> running
                      \-> crashed        -> respawn -> running (replay)

The job completes when every slot is terminal; the supervisor then restores
the final checkpoint for a held-out eval and returns history + bill.
"""

from __future__ import annotations

import dataclasses
import json
import os
import random
import shutil
import signal
import subprocess
import sys
import time
from typing import Any, Optional

import warnings

from repro.core.autotuner import (
    AutoTunerConfig,
    ScaleInAutoTuner,
    TopologyTuner,
    TopologyTunerConfig,
)
from repro.core.billing import CommModel, FaaSBill, faas_cost
from repro.runtime import protocol
from repro.runtime import workload as workload_lib
from repro.runtime.faults import (
    SUPERVISOR_KINDS,
    FaultEvent,
    FaultPlan,
    RetryPolicy,
)
from repro.wire import codec as wire_codec

PyTree = Any


@dataclasses.dataclass
class FaaSJobConfig:
    """One serverless training job (all fields JSON-serializable)."""

    run_dir: str
    workload: str = "pmf"
    workload_cfg: dict = dataclasses.field(default_factory=dict)
    n_workers: int = 4
    total_steps: int = 60
    invocation_steps: int = 1_000_000  # steps per function invocation
    checkpoint_every: int = 10
    optimizer: str = "nesterov"
    lr: float = 0.08
    isp_v: float = 0.7
    isp_decay: bool = True
    # pull-barrier consistency (DESIGN.md §13): 'isp' is the full per-step
    # barrier (default, bit-identical to pre-SSP builds); 'ssp' is bounded
    # staleness — a pull at step t blocks only until every update from
    # steps <= t - slack - 1 is stored, and is served exactly that step
    consistency: str = "isp"
    slack: int = 3
    # test/benchmark hook: {"worker": k, "delay_s": d, "every": n} makes
    # worker k sleep d seconds inside every n-th step's compute phase
    straggler: Optional[dict] = None
    # update wire encoding (repro.wire): 'auto'|'dense'|'sparse'|'bitmap',
    # optional 'fp16'|'bf16' value quantization with error-feedback residual
    wire_scheme: str = "auto"
    wire_quant: str = "none"
    # codec backend (repro.wire.codec.IMPLS): 'numpy' is the reference
    # path, 'pallas' the fused encode/decode kernels (bit-identical bytes,
    # kernels/wire_pack.py), 'auto' picks per leaf by size
    wire_impl: str = "numpy"
    # tuned worker launch env (launch/hostperf.py): tcmalloc LD_PRELOAD
    # when present, pinned XLA host flags, thread caps; the applied env is
    # recorded verbatim in the result under 'hostperf'
    hostperf: bool = False
    # update-store shards (paper: Redis instances) — the leaf-key partition
    # of runtime.sharding; bills as n_redis == n_brokers
    n_brokers: int = 1
    # worker<->shard data-path transport (DESIGN.md §12): 'tcp' is the
    # persistent loopback socket, 'shm' the supervisor-allocated
    # shared-memory ring segments (same framing/codec/accounted bytes);
    # the supervisor's own control plane always rides TCP
    transport: str = "tcp"
    shm_ring_bytes: int = 4 << 20  # per-direction ring capacity
    # split leaves denser than this many bytes into flat chunks before
    # shard assignment (0 = off) — topology-independent, so wire bytes
    # stay bit-identical across n_brokers; fixes the degenerate partition
    # of few-leaf models (PMF) at high shard counts
    shard_split_bytes: int = 0
    # pre-warmed invocation respawn (DESIGN.md §14.5): as a slot nears its
    # invocation boundary the supervisor pre-spawns the NEXT invocation
    # with --prewarm-gate — it connects, JIT-warms, and holds before
    # touching any state; at the boundary the supervisor opens the gate
    # instead of paying a cold start inside the barrier stall.  The
    # pre-spawned process's full lifetime is billed (it is a live
    # function), and the measured init overlap lands in the result.
    prewarm: bool = False
    autotune: bool = False
    tuner: Optional[AutoTunerConfig] = None
    # live topology autotuning (DESIGN.md §16): explore-then-commit over
    # {n_brokers, transport, wire_scheme, shard_split_bytes} cells with a
    # WAL-coordinated re-shard between cells.  Requires consistency='isp'
    # (SSP pulls read pre-fence steps) and no prewarm (a gated successor
    # spans the fence).  'partitioner' picks the leaf-key placement:
    # 'greedy' (default, bit-identical to every existing run) or 'ring'
    # (consistent hashing — minimal key movement across re-shards)
    topology_tune: bool = False
    partitioner: str = "greedy"
    topo_explore_steps: int = 6
    # deterministic test hooks
    scripted_evict_steps: tuple[int, ...] = ()
    # scripted topology changes: ((step, {knob: value, ...}), ...) — at
    # frontier >= step, re-shard to the given (partial) topology; the
    # deterministic twin of topology_tune the tests/CI drive
    scripted_retunes: tuple = ()
    kill_worker_at_step: Optional[tuple[int, int]] = None  # (worker, step)
    kill_broker_at_step: Optional[tuple[int, int]] = None  # (shard, step)
    # SIGKILL shard k right after its first migrate_read/migrate_in of a
    # handover — the replay-safety cell of the §16 failure matrix
    kill_broker_during_handover: Optional[int] = None
    # deterministic chaos plane (runtime/faults.py, DESIGN.md §17): an
    # expanded FaultPlan spec ({"seed": ..., "events": [...]}).  The
    # legacy kill_*_at_step / straggler knobs above compile into the same
    # plan, so every fault rides one mechanism
    chaos: Optional[dict] = None
    # unified RPC retry policy (faults.RetryPolicy.to_dict()) applied to
    # the supervisor's and the workers' broker RPCs; None = defaults
    rpc: Optional[dict] = None
    retain_updates: bool = False
    # housekeeping
    poll_interval_s: float = 0.05
    deadline_s: float = 600.0
    pull_deadline_s: float = 120.0
    broker_spawn_timeout_s: float = 30.0
    seed: int = 0

    def compiled_chaos_plan(self) -> Optional[FaultPlan]:
        """The job's effective fault plan: the explicit ``chaos`` spec
        merged with the legacy one-off knobs — ``kill_worker_at_step`` /
        ``kill_broker_at_step`` become supervisor kill events and
        ``straggler`` a repeating ``compute_delay``, so every fault rides
        the one seeded mechanism.  None when the job injects nothing."""
        plan = FaultPlan.from_spec(self.chaos)
        events = list(plan.events) if plan is not None else []
        seed = plan.seed if plan is not None else 0
        if self.kill_worker_at_step is not None:
            w, at = self.kill_worker_at_step
            events.append(FaultEvent("worker_kill", int(at), worker=int(w)))
        if self.kill_broker_at_step is not None:
            s, at = self.kill_broker_at_step
            events.append(FaultEvent("broker_kill", int(at), shard=int(s)))
        if self.straggler is not None:
            st = self.straggler
            events.append(FaultEvent(
                "compute_delay", 0, worker=int(st["worker"]),
                delay_s=float(st["delay_s"]), every=int(st.get("every", 1)),
            ))
        if not events:
            return None
        return FaultPlan(seed=seed, events=tuple(events)).validate()

    def to_dict(self) -> dict:
        """JSON round-trip for the out-of-process supervisor driver
        (``faults.run_job_resilient``); inverse of ``from_dict``."""
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "FaaSJobConfig":
        d = dict(d)
        if d.get("tuner"):
            d["tuner"] = AutoTunerConfig(**d["tuner"])
        d["scripted_evict_steps"] = tuple(
            d.get("scripted_evict_steps") or ())
        d["scripted_retunes"] = tuple(
            (int(s), dict(c)) for s, c in (d.get("scripted_retunes") or ()))
        for k in ("kill_worker_at_step", "kill_broker_at_step"):
            if d.get(k) is not None:
                d[k] = tuple(d[k])
        return cls(**d)

    def job_dict(self, n_batches: int) -> dict:
        d = {
            "workload": self.workload,
            "workload_cfg": dict(self.workload_cfg),
            "n_workers": self.n_workers,
            "total_steps": self.total_steps,
            "invocation_steps": self.invocation_steps,
            "checkpoint_every": self.checkpoint_every,
            "optimizer": self.optimizer,
            "lr": self.lr,
            "isp_v": self.isp_v,
            "isp_decay": self.isp_decay,
            "consistency": self.consistency,
            "slack": self.slack,
            "straggler": self.straggler,
            "wire_scheme": self.wire_scheme,
            "wire_quant": self.wire_quant,
            "wire_impl": self.wire_impl,
            "n_brokers": self.n_brokers,
            "transport": self.transport,
            "shard_split_bytes": self.shard_split_bytes,
            "partitioner": self.partitioner,
            "topo_gen": 0,
            "n_batches": n_batches,
            "run_dir": self.run_dir,
            "pull_deadline_s": self.pull_deadline_s,
            "seed": self.seed,
        }
        # keys absent on the default path: a chaos-free job's hello
        # response stays byte-identical to the wire baseline (the
        # 'straggler' key above is retained for the same reason — workers
        # now read its semantics from the compiled plan)
        plan = self.compiled_chaos_plan()
        if plan is not None:
            d["chaos"] = plan.to_spec()
        if self.rpc is not None:
            d["rpc"] = dict(self.rpc)
        return d


def _pid_alive(pid: Optional[int]) -> bool:
    """Liveness via /proc — works for ADOPTED processes (not our children,
    so waitpid is unavailable).  A zombie counts as dead: its exit status
    belongs to init, and it will never publish again."""
    if not pid:
        return False
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().rsplit(")", 1)[-1].split()[0] != "Z"
    except OSError:
        return False


def _terminate_pid(pid: int, grace_s: float = 5.0) -> None:
    """SIGTERM an adopted (non-child) process, escalating to SIGKILL."""
    try:
        os.kill(pid, signal.SIGTERM)
    except OSError:
        return
    deadline = time.monotonic() + grace_s
    while _pid_alive(pid) and time.monotonic() < deadline:
        time.sleep(0.05)
    if _pid_alive(pid):
        try:
            os.kill(pid, signal.SIGKILL)
        except OSError:
            pass


@dataclasses.dataclass
class _Slot:
    """One logical worker (survives respawns; one proc per invocation)."""

    worker: int
    proc: Optional[subprocess.Popen] = None
    # pid re-adopted from a previous supervisor's journal (not our child:
    # liveness comes from /proc, never waitpid)
    adopted_pid: Optional[int] = None
    spawned_at: float = 0.0
    invocations: int = 0
    terminal: Optional[str] = None  # 'done' | 'evicted'
    # first training step of the current invocation (restored + 1) — the
    # prewarm trigger predicts the boundary from it
    inv_start: int = 1
    # shm transport: current per-shard segment names (fresh per
    # invocation — the shm analogue of 'a new connection per invocation')
    shm_segs: list = dataclasses.field(default_factory=list)
    # pre-warmed next invocation (cfg.prewarm): a live process holding at
    # its gate, plus its own segment family and spawn timestamp.  All
    # prewarm timing is MONOTONIC — pre_ready_mono is the supervisor's
    # first sighting of the '.ready' marker (0.0 until seen), so the
    # overlap computation never mixes clock domains (a wall-clock step
    # used to be able to report negative or inflated overlaps)
    pre_proc: Optional[subprocess.Popen] = None
    pre_gate: Optional[str] = None
    pre_spawned_mono: float = 0.0
    pre_ready_mono: float = 0.0
    pre_shm_segs: list = dataclasses.field(default_factory=list)
    # parked at a topology fence: exited cleanly, respawns after handover
    held: bool = False

    @property
    def alive(self) -> bool:
        if self.proc is not None:
            return self.proc.poll() is None
        return _pid_alive(self.adopted_pid)

    @property
    def pid(self) -> Optional[int]:
        return self.proc.pid if self.proc is not None else self.adopted_pid


@dataclasses.dataclass
class _BrokerShard:
    """One update-store shard (survives respawns at a pinned port)."""

    shard: int
    proc: Optional[subprocess.Popen] = None
    adopted_pid: Optional[int] = None  # re-adopted from a journal
    addr: Optional[tuple[str, int]] = None
    spawns: int = 0

    @property
    def alive(self) -> bool:
        if self.proc is not None:
            return self.proc.poll() is None
        return _pid_alive(self.adopted_pid)

    @property
    def pid(self) -> Optional[int]:
        return self.proc.pid if self.proc is not None else self.adopted_pid


def _sigkill(obj) -> None:
    """SIGKILL a slot's or shard's process, spawned or adopted."""
    if obj.proc is not None:
        if obj.proc.poll() is None:
            obj.proc.send_signal(signal.SIGKILL)
    elif obj.adopted_pid is not None:
        try:
            os.kill(obj.adopted_pid, signal.SIGKILL)
        except OSError:
            pass


class Supervisor:
    def __init__(self, cfg: FaaSJobConfig, *, allow_self_kill: bool = False,
                 resume: bool = False):
        if cfg.transport not in ("tcp", "shm"):
            raise ValueError(
                f"transport must be 'tcp' or 'shm', got {cfg.transport!r}"
            )
        if cfg.consistency not in ("isp", "ssp"):
            raise ValueError(
                f"consistency must be 'isp' or 'ssp', got "
                f"{cfg.consistency!r}"
            )
        if cfg.consistency == "ssp" and cfg.slack < 0:
            raise ValueError(f"slack must be >= 0, got {cfg.slack}")
        if cfg.wire_impl not in wire_codec.IMPLS:
            raise ValueError(
                f"wire_impl must be one of {wire_codec.IMPLS}, got "
                f"{cfg.wire_impl!r}"
            )
        if cfg.partitioner not in ("greedy", "ring"):
            raise ValueError(
                f"partitioner must be 'greedy' or 'ring', got "
                f"{cfg.partitioner!r}"
            )
        retunes = []
        for step, changes in cfg.scripted_retunes or ():
            allowed = {"n_brokers", "transport", "wire_scheme",
                       "shard_split_bytes", "partitioner"}
            bad = set(changes) - allowed
            if bad:
                raise ValueError(f"scripted_retunes: unknown knobs {bad}")
            retunes.append((int(step), dict(changes)))
        if cfg.topology_tune or retunes:
            if cfg.consistency != "isp":
                # an SSP pull at step t is served step t - slack - 1 —
                # post-fence pulls would read pre-fence steps against a
                # re-sharded store; the fence argument is ISP-only
                raise ValueError(
                    "live re-sharding requires consistency='isp'"
                )
            if cfg.prewarm:
                raise ValueError(
                    "topology tuning is incompatible with prewarm: a "
                    "gated successor would span the epoch fence"
                )
        self.plan = cfg.compiled_chaos_plan()
        if self.plan is not None:
            for e in self.plan.events:
                if e.worker is not None and not 0 <= e.worker < cfg.n_workers:
                    raise ValueError(f"fault event targets worker "
                                     f"{e.worker} of {cfg.n_workers}: {e}")
                if e.shard is not None and not 0 <= e.shard < cfg.n_brokers:
                    raise ValueError(f"fault event targets shard "
                                     f"{e.shard} of {cfg.n_brokers}: {e}")
            if any(e.kind == "supervisor_kill" for e in self.plan.events):
                if not allow_self_kill:
                    raise ValueError(
                        "a supervisor_kill fault needs the out-of-process "
                        "driver (faults.run_job_resilient) — an in-process "
                        "supervisor cannot survive killing itself")
                if cfg.topology_tune or cfg.scripted_retunes:
                    raise ValueError(
                        "supervisor_kill is incompatible with live "
                        "re-sharding: handover state is not journaled")
        self._allow_self_kill = allow_self_kill
        self._resume = resume
        # the journal only pays for itself when a successor could read it
        self._journal_enabled = allow_self_kill or resume
        self._resumed = 0
        self._chaos_fired: set[int] = set()
        self._chaos_pending: list[dict] = []
        self.chaos_events: list[dict] = []
        self.rpc_policy = RetryPolicy.from_dict(cfg.rpc)
        self._t_job0 = time.monotonic()
        self.cfg = cfg
        self.wl = workload_lib.build(cfg.workload, cfg.workload_cfg)
        self.shards = [_BrokerShard(shard=s) for s in range(cfg.n_brokers)]
        self._conns: list[Optional[protocol.Connection]] = (
            [None] * cfg.n_brokers
        )
        self.slots = [_Slot(worker=w) for w in range(cfg.n_workers)]
        self.lifetimes: list[float] = []  # one entry per finished invocation
        self.history: list[dict] = []
        self.scale_events: list[dict] = []
        self.respawns: list[dict] = []
        self.broker_respawns: list[dict] = []
        self.cold_start_overlaps: list[dict] = []
        self.evictions: dict[int, int] = {}
        self._frontier = 0
        self._poll_since = 1  # next telemetry step this supervisor hasn't seen
        self._scripted_fired = 0
        self._stopping = False  # end-of-job: shard exits are intentional
        # shm transport: job-unique segment namespace + live segments
        # (the supervisor is the single owner of create/unlink)
        import secrets

        self._shm_token = f"ml{os.getpid():x}{secrets.token_hex(2)}"
        self._shm_segments: dict[str, Any] = {}  # name -> wire.shm.Segment
        self.hostperf_applied: Optional[dict] = None
        self.tuner: Optional[ScaleInAutoTuner] = None
        if cfg.autotune:
            self.tuner = ScaleInAutoTuner(
                cfg.tuner or AutoTunerConfig(), cfg.n_workers
            )
        # live topology state (DESIGN.md §16): the CURRENT knob values —
        # cfg keeps the job's starting point, self.topology what is
        # actually running now
        self.topology = {
            "n_brokers": cfg.n_brokers,
            "transport": cfg.transport,
            "wire_scheme": cfg.wire_scheme,
            "shard_split_bytes": cfg.shard_split_bytes,
            "partitioner": cfg.partitioner,
        }
        self.topo_gen = 0
        self._max_brokers = cfg.n_brokers  # peak shard count → n_redis bill
        self._handover: Optional[dict] = None  # {"fence", "changes"}
        self._retunes_pending = retunes
        self._topo_kill_armed = cfg.kill_broker_during_handover is not None
        self.retired_shard_stats: list[dict] = []
        self.topology_events: list[dict] = []
        self._topo_cell_start = 1  # first step measured for the active cell
        self.topo_tuner: Optional[TopologyTuner] = None
        if cfg.topology_tune and not retunes:
            cur = dict(self.topology)
            flip_brokers = dict(cur,
                                n_brokers=2 if cur["n_brokers"] == 1 else 1)
            flip_transport = dict(
                cur, transport="shm" if cur["transport"] == "tcp" else "tcp"
            )
            self.topo_tuner = TopologyTuner(
                [cur, flip_brokers, flip_transport],
                TopologyTunerConfig(explore_steps=cfg.topo_explore_steps),
                comm=CommModel(),
                n_workers=cfg.n_workers,
            )

    # -- process management ---------------------------------------------------

    def _base_env(self) -> dict:
        import repro

        # repro may be a namespace package (no __init__.py): use __path__
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
        if self.cfg.hostperf:
            # tuned launch env (launch/hostperf.py): tcmalloc preload when
            # available, pinned XLA host flags, full thread-cap family; what
            # was actually applied is recorded in self.hostperf_applied
            from repro.launch import hostperf

            env = hostperf.build_env(env, threads=1)
            self.hostperf_applied = hostperf.describe(env)
            return env
        # each worker is the paper's 1 vCPU function: cap per-process math
        # threads so N workers on an M-core host don't thrash each other
        # (oversubscribed intra-op parallelism was the dominant measured
        # compute overhead on small hosts — see BENCH_runtime.json phases)
        env.setdefault("XLA_FLAGS", "--xla_cpu_multi_thread_eigen=false "
                       "intra_op_parallelism_threads=1")
        env.setdefault("OMP_NUM_THREADS", "1")
        env.setdefault("OPENBLAS_NUM_THREADS", "1")
        return env

    # -- broker shard lifecycle -----------------------------------------------

    def _broker_dir(self) -> str:
        return os.path.join(self.cfg.run_dir, "broker")

    def _spawn_broker(self, bs: _BrokerShard) -> None:
        """Spawn (or respawn) one shard process and wait until it listens.

        First spawn binds an ephemeral port; respawns pin the original port
        so the workers' persistent connections reconnect unchanged.  The
        port file doubles as the readiness signal — the shard writes it
        only after any WAL replay completed and the socket is bound.
        """
        bdir = self._broker_dir()
        os.makedirs(bdir, exist_ok=True)
        logdir = os.path.join(self.cfg.run_dir, "logs")
        os.makedirs(logdir, exist_ok=True)
        port_file = os.path.join(bdir, f"shard{bs.shard:02d}.port")
        if os.path.exists(port_file):
            os.unlink(port_file)
        wal_path = os.path.join(bdir, f"shard{bs.shard:02d}.wal")
        if bs.spawns == 0 and os.path.exists(wal_path):
            # a reused run_dir must not replay the PREVIOUS job's log into
            # a fresh one; only respawns within this job replay the WAL
            os.unlink(wal_path)
        log = open(
            os.path.join(
                logdir, f"broker{bs.shard:02d}.spawn{bs.spawns:02d}.log"
            ),
            "wb",
        )
        bs.adopted_pid = None
        bs.proc = subprocess.Popen(
            [
                sys.executable,
                "-m",
                "repro.runtime.broker",
                "--config", os.path.join(bdir, "job.json"),
                "--shard-id", str(bs.shard),
                "--n-shards", str(len(self.shards)),
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
        deadline = time.monotonic() + self.cfg.broker_spawn_timeout_s
        while not os.path.exists(port_file):
            if bs.proc.poll() is not None:
                raise RuntimeError(
                    f"broker shard {bs.shard} exited during spawn "
                    f"(code {bs.proc.returncode}); logs in {logdir}"
                )
            if time.monotonic() > deadline:
                raise RuntimeError(
                    f"broker shard {bs.shard} did not listen within "
                    f"{self.cfg.broker_spawn_timeout_s}s"
                )
            time.sleep(0.01)
        with open(port_file) as f:
            host, port = f.read().strip().rsplit(":", 1)
        bs.addr = (host, int(port))

    def _start_brokers(self) -> None:
        bdir = self._broker_dir()
        os.makedirs(bdir, exist_ok=True)
        with open(os.path.join(bdir, "job.json"), "w") as f:
            json.dump(self.cfg.job_dict(self.wl.n_batches), f, indent=1)
        for bs in self.shards:
            self._spawn_broker(bs)

    def _reap_brokers(self) -> None:
        """Respawn any shard that died without being asked to — the WAL
        replay restores its store; workers ride the gap on RPC retries."""
        if self._stopping:
            # shutdown phase: shards exit on purpose after acking their
            # shutdown RPC — respawning one here (e.g. from a _rpc retry
            # whose response was lost) would hand back a fresh process
            # with empty socket stats and a phantom respawn entry
            return
        for bs in self.shards:
            exited = (
                bs.proc.poll() is not None if bs.proc is not None
                else bs.adopted_pid is not None
                and not _pid_alive(bs.adopted_pid)
            )
            if exited:
                self.broker_respawns.append(
                    {
                        "shard": bs.shard,
                        "exit_code": (
                            bs.proc.returncode if bs.proc is not None
                            else None  # adopted: init reaped the status
                        ),
                        "at_frontier": self._frontier,
                    }
                )
                # drop the stale client connection before the port rebinds
                if self._conns[bs.shard] is not None:
                    self._conns[bs.shard].close()
                    self._conns[bs.shard] = None
                self._spawn_broker(bs)
                if self.topology["transport"] == "shm":
                    # the shard's shm serving threads died with it: hand
                    # it every live worker's segment again (each re-serve
                    # resets that ring pair and bumps its generation, so
                    # in-flight workers replay through the same retry
                    # window TCP reconnects use)
                    self._reserve_shard_shm(bs)

    # -- shared-memory segment lifecycle --------------------------------------
    #
    # The supervisor is the single owner of segment create/unlink (workers
    # and brokers only ever attach): one segment per (worker, shard),
    # recreated FRESH for every worker invocation — the shm analogue of
    # 'a new connection per invocation', which is what makes respawn after
    # a SIGKILL race-free (a dying invocation's half-written rings are
    # never reused; its broker-side threads exit on client-death
    # detection and the supervisor unlinks the memory).

    def _teardown_worker_shm(self, slot: _Slot) -> None:
        from repro.wire import shm

        for name in slot.shm_segs:
            seg = self._shm_segments.pop(name, None)
            if seg is not None:
                seg.unlink()
            else:  # pragma: no cover - belt and braces
                shm.Segment.unlink_by_name(name)
        slot.shm_segs = []

    def _setup_worker_shm(self, slot: _Slot) -> str:
        """(Re)allocate fresh segments for this slot's next invocation and
        hand them to every shard to serve; returns the worker's segment
        base name (shard s attaches '<base>s<s>')."""
        from repro.wire import shm

        self._teardown_worker_shm(slot)
        base = f"{self._shm_token}w{slot.worker}i{slot.invocations}"
        names = [f"{base}s{s}" for s in range(len(self.shards))]
        for name in names:
            self._shm_segments[name] = shm.Segment.create(
                name, ring_bytes=self.cfg.shm_ring_bytes
            )
        for s, name in enumerate(names):
            resp, _ = self._rpc({"t": "shm_serve", "seg": name}, shard=s)
            if not resp.get("ok"):  # pragma: no cover - defensive
                raise RuntimeError(f"shard {s} refused shm_serve: {resp}")
        slot.shm_segs = names
        return base

    def _reserve_shard_shm(self, bs: "_BrokerShard") -> None:
        """After a broker-shard respawn: hand the fresh process every live
        worker's segment for this shard again (its serving threads died
        with it).  Direct one-shot RPCs to the just-bound port — this is
        called from inside ``_rpc``'s retry path, so it must not recurse
        into ``_rpc`` itself."""
        for slot in self.slots:
            if slot.terminal is not None or not slot.shm_segs:
                continue
            name = slot.shm_segs[bs.shard]
            for attempt in range(3):
                try:
                    protocol.request(
                        bs.addr, {"t": "shm_serve", "seg": name},
                        timeout=10.0,
                    )
                    break
                except (ConnectionError, OSError, TimeoutError):
                    if attempt == 2:
                        # workers ride it out: their shm connect wait +
                        # RPC retries outlast the next reap cycle
                        break
                    time.sleep(0.2)

    # -- worker lifecycle -----------------------------------------------------

    def _spawn(self, slot: _Slot) -> None:
        logdir = os.path.join(self.cfg.run_dir, "logs")
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
            sys.executable,
            "-m",
            "repro.runtime.worker",
            "--brokers",
            brokers,
            "--worker-id",
            str(slot.worker),
        ]
        if self.topology["transport"] == "shm":
            cmd += [
                "--transport", "shm",
                "--shm-seg", self._setup_worker_shm(slot),
            ]
        slot.adopted_pid = None
        slot.proc = subprocess.Popen(
            cmd,
            stdout=log,
            stderr=subprocess.STDOUT,
            env=self._worker_env(),
        )
        log.close()
        slot.spawned_at = time.monotonic()
        slot.invocations += 1
        slot.inv_start = self._restored_step(slot) + 1

    def _restored_step(self, slot: _Slot) -> int:
        from repro.checkpoint import store as ckpt

        return ckpt.latest_step(
            os.path.join(self.cfg.run_dir, "ckpt", f"w{slot.worker:03d}")
        ) or 0

    # -- pre-warmed respawn (DESIGN.md §14.5) ----------------------------------

    def _setup_prewarm_shm(self, slot: _Slot) -> str:
        """Fresh segments for the NEXT invocation, created alongside the
        current invocation's live ones (never torn down here)."""
        from repro.wire import shm

        base = f"{self._shm_token}w{slot.worker}i{slot.invocations}"
        names = [f"{base}s{s}" for s in range(len(self.shards))]
        for name in names:
            self._shm_segments[name] = shm.Segment.create(
                name, ring_bytes=self.cfg.shm_ring_bytes
            )
        for s, name in enumerate(names):
            resp, _ = self._rpc({"t": "shm_serve", "seg": name}, shard=s)
            if not resp.get("ok"):  # pragma: no cover - defensive
                raise RuntimeError(f"shard {s} refused shm_serve: {resp}")
        slot.pre_shm_segs = names
        return base

    def _prespawn(self, slot: _Slot) -> None:
        """Spawn the slot's next invocation gated: it imports, connects,
        JIT-warms and then holds at ``pre_gate`` — runtime init runs
        under the tail of the current invocation instead of inside the
        respawn stall."""
        logdir = os.path.join(self.cfg.run_dir, "logs")
        gatedir = os.path.join(self.cfg.run_dir, "gate")
        os.makedirs(logdir, exist_ok=True)
        os.makedirs(gatedir, exist_ok=True)
        gate = os.path.join(
            gatedir, f"w{slot.worker:03d}.inv{slot.invocations:03d}.gate"
        )
        for p in (gate, gate + ".ready"):
            if os.path.exists(p):  # pragma: no cover - stale reuse
                os.unlink(p)
        log = open(
            os.path.join(
                logdir,
                f"w{slot.worker:03d}.inv{slot.invocations:03d}.pre.log",
            ),
            "wb",
        )
        brokers = ",".join(f"{h}:{p}" for h, p in
                           (bs.addr for bs in self.shards))
        cmd = [
            sys.executable,
            "-m",
            "repro.runtime.worker",
            "--brokers", brokers,
            "--worker-id", str(slot.worker),
            "--prewarm-gate", gate,
        ]
        if self.topology["transport"] == "shm":
            cmd += ["--transport", "shm",
                    "--shm-seg", self._setup_prewarm_shm(slot)]
        slot.pre_proc = subprocess.Popen(
            cmd,
            stdout=log,
            stderr=subprocess.STDOUT,
            env=self._worker_env(),
        )
        log.close()
        slot.pre_gate = gate
        slot.pre_spawned_mono = time.monotonic()
        slot.pre_ready_mono = 0.0

    def _scan_prewarm_ready(self) -> None:
        """Stamp the first MONOTONIC sighting of each pre-warming slot's
        '.ready' marker — the supervisor's own clock, so the overlap
        computation never reads a file mtime from the wall-clock domain
        (which can step and report negative/inflated overlaps)."""
        for slot in self.slots:
            if (
                slot.pre_proc is not None
                and slot.pre_gate is not None
                and slot.pre_ready_mono == 0.0
                and os.path.exists(slot.pre_gate + ".ready")
            ):
                slot.pre_ready_mono = time.monotonic()

    def _promote_prewarmed(self, slot: _Slot) -> None:
        """The current invocation ended and a pre-warmed successor is
        holding at its gate: open the gate and make it THE invocation.
        Records the measured init overlap — the cold-start seconds the
        barrier never saw."""
        self._scan_prewarm_ready()
        now_mono = time.monotonic()
        warm = slot.pre_ready_mono > 0.0
        # overlapped cold-start seconds: init time the successor spent
        # under the previous invocation — up to the ready sighting when it
        # finished warming in time, else everything it got so far (it is
        # still warming, but those seconds were still hidden).  Pure
        # monotonic delta; a negative value can only mean a bookkeeping
        # bug, so clamp loudly rather than record garbage.
        end = slot.pre_ready_mono if warm else now_mono
        overlap = end - slot.pre_spawned_mono
        if overlap < 0.0:  # pragma: no cover - defensive
            warnings.warn(
                f"negative prewarm overlap ({overlap:.3f}s) for worker "
                f"{slot.worker}; clamping to 0",
                stacklevel=2,
            )
            overlap = 0.0
        self.cold_start_overlaps.append(
            {
                "worker": slot.worker,
                "invocation": slot.invocations,
                "overlap_s": overlap,
                "ready_at_promotion": warm,
            }
        )
        # open the gate (atomic create): the held process restores the
        # newest checkpoint — written by the invocation that just exited —
        # and starts training
        tmp = slot.pre_gate + ".tmp"
        with open(tmp, "w"):
            pass
        os.replace(tmp, slot.pre_gate)
        # the old invocation's segments die with it; the promoted one
        # already owns a served family
        self._teardown_worker_shm(slot)
        slot.shm_segs, slot.pre_shm_segs = slot.pre_shm_segs, []
        slot.proc = slot.pre_proc
        slot.spawned_at = slot.pre_spawned_mono
        slot.pre_proc = None
        slot.pre_gate = None
        slot.invocations += 1
        slot.inv_start = self._restored_step(slot) + 1

    def _abort_prewarmed(self, slot: _Slot) -> None:
        """The slot went terminal with a successor still holding at its
        gate: kill it and bill its (real, live-function) lifetime."""
        if slot.pre_proc is None:
            return
        if slot.pre_proc.poll() is None:
            slot.pre_proc.kill()
            slot.pre_proc.wait()
        self.lifetimes.append(time.monotonic() - slot.pre_spawned_mono)
        slot.pre_proc = None
        slot.pre_gate = None
        for name in slot.pre_shm_segs:
            seg = self._shm_segments.pop(name, None)
            if seg is not None:
                seg.unlink()
        slot.pre_shm_segs = []

    def _maybe_prespawn(self) -> None:
        """Fire a gated successor for every slot within one step of its
        invocation boundary (predicted from the invocation's start step
        and budget) that doesn't have one yet."""
        if not self.cfg.prewarm or self._handover is not None:
            return
        if self.cfg.invocation_steps > self.cfg.total_steps:
            return  # single-invocation job: no boundary to warm for
        for slot in self.slots:
            if (
                slot.terminal is not None
                or not slot.alive
                or slot.pre_proc is not None
                or slot.worker in self.evictions
            ):
                continue
            boundary = slot.inv_start + self.cfg.invocation_steps - 1
            if boundary > self.cfg.total_steps:
                continue  # final invocation: nothing follows it
            if self._frontier >= boundary - 1:
                self._prespawn(slot)

    def _reap(self, slot: _Slot, statuses: dict) -> None:
        """Classify an exited process and respawn when the slot lives on."""
        assert slot.proc is not None or slot.adopted_pid is not None
        # an adopted process was reaped by init: no exit code to read
        code = slot.proc.returncode if slot.proc is not None else None
        self.lifetimes.append(time.monotonic() - slot.spawned_at)
        status = statuses.get(str(slot.worker), "")
        slot.proc = None
        slot.adopted_pid = None
        if status == "bye:done":
            slot.terminal = "done"
            self._teardown_worker_shm(slot)
            self._abort_prewarmed(slot)
        elif status == "bye:evicted":
            slot.terminal = "evicted"
            self._teardown_worker_shm(slot)
            self._abort_prewarmed(slot)
        elif status == "bye:topo-fence":
            # parked at the topology epoch fence (DESIGN.md §16): its
            # fence-1 checkpoint is durable; the slot respawns only after
            # the handover migrated the store (its segments die now — a
            # transport switch may mean the next invocation isn't shm)
            self._teardown_worker_shm(slot)
            self._abort_prewarmed(slot)
            slot.held = True
        elif status == "bye:invocation-end":
            # next invocation of the same function — pre-warmed and held
            # at its gate when cfg.prewarm got it ready in time
            if slot.pre_proc is not None and slot.pre_proc.poll() is None:
                self._promote_prewarmed(slot)
            else:
                self._abort_prewarmed(slot)
                self._spawn(slot)
        else:
            # no goodbye: the process died (e.g. SIGKILL) — respawn; the
            # worker restores its newest checkpoint and replays forward.
            # A held pre-warmed successor is an equally valid respawn: it
            # restores the newest checkpoint only after its gate opens.
            restored = self._restored_step(slot)
            self.respawns.append(
                {
                    "worker": slot.worker,
                    "exit_code": code,
                    "restored_step": restored,
                    "at_frontier": self._frontier,
                }
            )
            if slot.pre_proc is not None and slot.pre_proc.poll() is None:
                self._promote_prewarmed(slot)
            else:
                self._abort_prewarmed(slot)
                self._spawn(slot)

    # -- broker RPC -----------------------------------------------------------

    def _rpc(
        self, header: dict, payload: bytes = b"", shard: int = 0,
        tries: Optional[int] = None,
    ) -> tuple[dict, bytes]:
        """Retrying RPC to one shard — must survive a shard respawn window
        (the connection reconnects to the pinned port once it rebinds).
        Attempt timeout, count, backoff and deadline all come from the
        job's ``RetryPolicy`` (``cfg.rpc``; ``tries`` overrides the count
        for callers with their own bound)."""
        policy = (
            self.rpc_policy if tries is None
            else dataclasses.replace(self.rpc_policy, tries=tries)
        )
        last: Optional[Exception] = None
        for _ in policy.attempts():
            if self._conns[shard] is None:
                self._conns[shard] = protocol.Connection(
                    self.shards[shard].addr, timeout=policy.timeout_s
                )
            try:
                return self._conns[shard].request(header, payload)
            except (ConnectionError, OSError, TimeoutError) as e:
                last = e
                self._conns[shard].close()
                self._conns[shard] = None
                self._reap_brokers()  # a dead shard blocks every retry
        assert last is not None
        raise last

    def _poll(self) -> dict:
        # supervisor-owned cursor keeps the poll idempotent: if the
        # connection retries a poll whose response was lost, the broker
        # re-serves the same rows instead of dropping them
        resp, _ = self._rpc({"t": "poll", "since": self._poll_since})
        for row in resp["rows"]:
            self.history.append(row)
            self._poll_since = row["step"] + 1
            self._frontier = max(self._frontier, row["step"])
            if self.tuner is not None:
                self.tuner.observe(row["step"], row["loss"], row["dur_s"])
            if (
                self.topo_tuner is not None
                and row["step"] >= self._topo_cell_start
            ):
                # steps before the cell boundary belong to the previous
                # topology — feeding them would pollute the new cell's p50
                self.topo_tuner.observe(row["dur_s"], row.get("phase"))
        self.evictions = {int(k): v for k, v in resp["evictions"].items()}
        return resp

    def _evict_victim(self, reason: str, s_delta=None) -> bool:
        """Highest-id live, non-terminal, non-evicted worker leaves."""
        victims = [
            s.worker
            for s in self.slots
            if s.terminal is None and s.worker not in self.evictions
        ]
        if len(victims) <= 1:
            return False
        victim = max(victims)
        resp, _ = self._rpc({"t": "evict", "worker": victim})
        if not resp.get("granted"):
            return False  # e.g. past-end: the job ends before it could land
        # install the coordinator-granted (worker, step) on every other
        # shard: until the sync lands a stale shard only *blocks* its
        # step-e barrier (it still expects the leaver's publish), so the
        # window is safe — see DESIGN.md §11 failure matrix
        for s in range(1, len(self.shards)):
            self._rpc(
                {"t": "evict_apply", "worker": victim,
                 "step": resp["evict_step"]},
                shard=s,
            )
        # record immediately — a second decision in this same poll iteration
        # must not re-target the worker we just evicted
        self.evictions[victim] = resp["evict_step"]
        self.scale_events.append(
            {
                "worker": victim,
                "evict_step": resp["evict_step"],
                "at_frontier": self._frontier,
                "s_delta": s_delta,
                "reason": reason,
            }
        )
        return True

    # -- live topology handover (DESIGN.md §16) --------------------------------

    def _initiate_retune(self, changes: dict) -> bool:
        """Ask the coordinator for an epoch fence toward ``changes``.
        Returns True when the request is settled (handover pending, or a
        no-op because nothing actually changes), False when the
        coordinator refused (past-end) — a permanent refusal."""
        diff = {
            k: v for k, v in changes.items() if self.topology.get(k) != v
        }
        if not diff:
            self.topology_events.append(
                {"gen": self.topo_gen, "fence": None, "changes": {},
                 "noop": True, "at_frontier": self._frontier}
            )
            return True
        resp, _ = self._rpc({"t": "topo_begin"})
        if not resp.get("granted"):
            self.topology_events.append(
                {"gen": self.topo_gen, "fence": None, "changes": diff,
                 "refused": resp.get("reason", "?"),
                 "at_frontier": self._frontier}
            )
            return False
        self._handover = {"fence": int(resp["fence"]), "changes": diff}
        return True

    def _complete_handover(self) -> None:
        """Every live worker is parked at the fence with a durable
        fence-1 checkpoint: migrate the moved identities, commit the new
        topology, respawn.  Every mutation rides the shards' WALs and the
        idempotent migrate ops, so a SIGKILL on either side of any
        migration replays to bit-identical state."""
        from repro.runtime import sharding

        hand = self._handover
        assert hand is not None
        fence = hand["fence"]
        # drain the final pre-fence telemetry so the tuner's cell
        # accounting closes at the boundary
        self._poll()

        old = dict(self.topology)
        new = dict(old, **hand["changes"])
        old_n, new_n = len(self.shards), int(new["n_brokers"])
        params0 = self.wl.params0
        a_old = sharding.tree_assignment(
            params0, old_n, split_bytes=int(old["shard_split_bytes"]),
            partitioner=old["partitioner"],
        )
        a_new = sharding.tree_assignment(
            params0, new_n, split_bytes=int(new["shard_split_bytes"]),
            partitioner=new["partitioner"],
        )
        owner_new = sharding.offset_owner(
            params0, int(new["shard_split_bytes"]), a_new
        )
        # stored pre-fence entries are chunked at the OLD threshold: each
        # old chunk moves to the new owner of the new chunk containing its
        # start offset — totality is preserved, which is all pre-fence
        # data needs (post-fence pulls never read pre-fence steps)
        subleaves = sharding.tree_subleaves(
            params0, int(old["shard_split_bytes"])
        )
        moves: dict[tuple[int, int], list] = {}
        for leaf_key, subkey, off, _n in subleaves:
            src = a_old[subkey]
            dest = owner_new(leaf_key, off)
            if src != dest:
                moves.setdefault((src, dest), []).append([leaf_key, off])
        gen = self.topo_gen + 1

        # rewrite job.json FIRST: every shard (re)spawned from here reads
        # the new topology.  Old shards re-reading it mid-migration is
        # harmless — their store rebuilds from the WAL and the migrate ops
        # never consult the config
        job = self.cfg.job_dict(self.wl.n_batches)
        job.update(
            n_brokers=new_n,
            transport=new["transport"],
            wire_scheme=new["wire_scheme"],
            shard_split_bytes=new["shard_split_bytes"],
            partitioner=new["partitioner"],
            topo_gen=gen,
        )
        with open(os.path.join(self._broker_dir(), "job.json"), "w") as f:
            json.dump(job, f, indent=1)

        if new_n > old_n:
            # grow: append ALL new shard slots first (len(self.shards) is
            # the --n-shards every spawn reads), then spawn + install the
            # eviction table so the new barriers agree on membership
            for s in range(old_n, new_n):
                self.shards.append(_BrokerShard(shard=s))
                self._conns.append(None)
            for s in range(old_n, new_n):
                self._spawn_broker(self.shards[s])
                for w, estep in self.evictions.items():
                    self._rpc({"t": "evict_apply", "worker": w,
                               "step": estep}, shard=s)

        kill_shard = self.cfg.kill_broker_during_handover
        moved_subkeys = 0
        for (src, dest) in sorted(moves):
            moved = moves[(src, dest)]
            moved_subkeys += len(moved)
            resp, blob = self._rpc(
                {"t": "migrate_read", "moved": moved}, shard=src
            )
            if kill_shard is not None and self._topo_kill_armed:
                # §16 failure-matrix cell: SIGKILL a shard mid-handover;
                # _rpc retries ride the respawn+WAL-replay and the
                # idempotent migrate ops land bit-identical state
                self._topo_kill_armed = False
                bs = self.shards[kill_shard]
                if bs.alive:
                    bs.proc.send_signal(signal.SIGKILL)
            self._rpc(
                {"t": "migrate_in", "gen": gen, "src": src,
                 "parts": resp["parts"]},
                payload=blob, shard=dest,
            )
        # drop only after EVERY destination acked its migrate_in: a source
        # with several destinations must not lose unread slices
        for src in sorted({s for s, _ in moves}):
            moved = [
                m for (s, _d), ms in moves.items() if s == src for m in ms
            ]
            self._rpc({"t": "migrate_drop", "moved": moved}, shard=src)

        # commit on every shard of the NEW topology (clears the fence on
        # the coordinator; updates the job dict respawned workers hello
        # into); retired shards get a shutdown instead
        for s in range(new_n):
            self._rpc(
                {"t": "topo_commit", "gen": gen, "n_shards": new_n,
                 "n_brokers": new_n, "transport": new["transport"],
                 "wire_scheme": new["wire_scheme"],
                 "shard_split_bytes": new["shard_split_bytes"],
                 "partitioner": new["partitioner"]},
                shard=s,
            )
        if new_n < old_n:
            # shrink: the move map emptied shards >= new_n; retire them
            # synchronously (no _rpc between terminate and truncation, or
            # a retry's _reap_brokers would respawn a retired shard)
            for s in range(new_n, old_n):
                bs = self.shards[s]
                try:
                    r, _ = self._rpc({"t": "shutdown"}, shard=s)
                    self.retired_shard_stats.append(r)
                except Exception:  # pragma: no cover - defensive
                    self.retired_shard_stats.append({"shard_id": s})
                if self._conns[s] is not None:
                    self._conns[s].close()
                if bs.proc is not None:
                    bs.proc.terminate()
                    try:
                        bs.proc.wait(timeout=5.0)
                    except subprocess.TimeoutExpired:
                        bs.proc.kill()
            del self.shards[new_n:]
            del self._conns[new_n:]

        self.topology = new
        self.topo_gen = gen
        self._max_brokers = max(self._max_brokers, new_n)
        self._topo_cell_start = fence
        self.topology_events.append(
            {
                "gen": gen,
                "fence": fence,
                "changes": hand["changes"],
                "moved_subkeys": moved_subkeys,
                "total_subkeys": len(subleaves),
                "at_frontier": self._frontier,
            }
        )
        self._handover = None
        if self.topo_tuner is not None:
            # observations from here on belong to the next cell (the
            # entry _poll above closed the old cell's rows)
            self.topo_tuner.cell_started()
        for slot in self.slots:
            if slot.held:
                slot.held = False
                self._spawn(slot)

    # -- chaos plane (runtime/faults.py, DESIGN.md §17) ------------------------

    def _chaos_step(self) -> None:
        """Fire due supervisor-side fault events, then settle in-flight
        recoveries (a fault's ``recovery_s`` closes when the supervisor
        observes the victim back: worker respawned, shard rebound)."""
        if self.plan is not None:
            for idx, e in enumerate(self.plan.events):
                if (
                    e.kind not in SUPERVISOR_KINDS
                    or idx in self._chaos_fired
                    or self._frontier < e.step
                ):
                    continue
                self._chaos_fired.add(idx)
                self._inject(idx, e)
        self._settle_chaos()

    def _inject(self, idx: int, e: FaultEvent) -> None:
        rec = {"index": idx, "kind": e.kind, "step": e.step,
               "at_frontier": self._frontier}
        if e.kind == "worker_kill":
            slot = self.slots[e.worker]
            rec["worker"] = e.worker
            if slot.terminal is not None or not slot.alive:
                rec["skipped"] = "victim not running"
                self.chaos_events.append(rec)
                return
            _sigkill(slot)
            self._chaos_pending.append(
                {"rec": rec, "t0": time.monotonic(), "kind": e.kind,
                 "worker": e.worker, "invocations": slot.invocations})
        elif e.kind in ("broker_kill", "wal_corrupt"):
            bs = self.shards[e.shard]
            rec["shard"] = e.shard
            if not bs.alive:
                rec["skipped"] = "shard not running"
                self.chaos_events.append(rec)
                return
            _sigkill(bs)
            if e.kind == "wal_corrupt":
                rec["flipped_offset"] = self._flip_wal_byte(e.shard, idx)
            self._chaos_pending.append(
                {"rec": rec, "t0": time.monotonic(), "kind": e.kind,
                 "shard": e.shard, "spawns": bs.spawns})
        elif e.kind == "supervisor_kill":
            # journal first (chaos_fired already holds this index, so the
            # successor will not re-fire it), then die for real — no
            # cleanup, no goodbye: the pool keeps running headless until
            # the next supervisor re-adopts it from the journal
            rec["killed_at_wall"] = time.time()
            self.chaos_events.append(rec)
            self._save_journal()
            os.kill(os.getpid(), signal.SIGKILL)

    def _flip_wal_byte(self, shard: int, idx: int) -> Optional[int]:
        """Flip one seeded byte in the tail third of a (just-killed)
        shard's WAL — the respawn's CRC check quarantines from there."""
        assert self.plan is not None
        path = os.path.join(self._broker_dir(), f"shard{shard:02d}.wal")
        try:
            size = os.path.getsize(path)
        except OSError:
            return None
        if size == 0:
            return None
        rng = random.Random((self.plan.seed << 8) ^ (0x5A5A + idx))
        pos = rng.randrange(size - max(size // 3, 1), size)
        with open(path, "r+b") as f:
            f.seek(pos)
            b = f.read(1)
            f.seek(pos)
            f.write(bytes([b[0] ^ 0xFF]))
            f.flush()
            os.fsync(f.fileno())
        return pos

    def _settle_chaos(self) -> None:
        still = []
        for p in self._chaos_pending:
            rec = p["rec"]
            if p["kind"] == "worker_kill":
                slot = self.slots[p["worker"]]
                done = slot.terminal is not None or (
                    slot.invocations > p["invocations"] and slot.alive)
            else:  # broker_kill / wal_corrupt: settled once rebound
                bs = self.shards[p["shard"]]
                done = bs.spawns > p["spawns"] and bs.alive
                if done and p["kind"] == "wal_corrupt":
                    rec["rollback"] = self._quarantine_rollback(p["shard"])
            if done:
                rec["recovery_s"] = time.monotonic() - p["t0"]
                self.chaos_events.append(rec)
            else:
                still.append(p)
        self._chaos_pending = still

    def _prune_checkpoints(self, worker: int, limit: int) -> list[int]:
        from repro.checkpoint import store as ckpt

        d = os.path.join(self.cfg.run_dir, "ckpt", f"w{worker:03d}")
        pruned = []
        for step in ckpt.all_steps(d):
            if step > limit:
                shutil.rmtree(os.path.join(d, f"step_{step:010d}"),
                              ignore_errors=True)
                pruned.append(step)
        return pruned

    def _quarantine_rollback(self, shard: int) -> list[dict]:
        """Reconcile the pool with a shard that lost a WAL suffix.

        The respawned shard's per-worker publish ``clocks`` are its
        durable frontier — anything a worker published past its clock on
        this shard is gone (quarantined, or silently torn off when the
        flip hit a length field of the final record, which is why this
        runs unconditionally after every wal_corrupt injection).  Roll
        every non-terminal worker back to that frontier: SIGKILL it and
        prune its checkpoints past the clock, so the normal crash-respawn
        path replays forward and re-publishes the lost records
        bit-identically (the other shards dup-check the duplicates)."""
        bs = self.shards[shard]
        try:
            resp, _ = protocol.request(
                bs.addr, {"t": "poll", "since": self.cfg.total_steps + 1},
                timeout=10.0,
            )
        except (ConnectionError, OSError, TimeoutError):
            return []  # shard died again; the next reap cycle recovers
        clocks = {int(k): v for k, v in (resp.get("clocks") or {}).items()}
        rolled = []
        for slot in self.slots:
            if slot.terminal is not None:
                continue
            limit = clocks.get(slot.worker, 0)
            pruned = self._prune_checkpoints(slot.worker, limit)
            if slot.alive:
                _sigkill(slot)
            rolled.append({"worker": slot.worker, "replay_from": limit,
                           "pruned_ckpts": pruned})
        return rolled

    # -- crash journal + re-adoption (DESIGN.md §17.4) -------------------------

    def _journal_path(self) -> str:
        return os.path.join(self.cfg.run_dir, "supervisor.journal.json")

    def _save_journal(self) -> None:
        """Atomically persist everything a successor supervisor needs to
        re-adopt the live pool: pids, ports, invocation counters and the
        billing/telemetry accumulators.  Monotonic timestamps are stored
        as wall-clock so the successor can rebase them onto its own
        monotonic domain."""
        if not self._journal_enabled:
            return
        now_m, now_w = time.monotonic(), time.time()
        state = {
            "version": 1,
            "t_job0_wall": now_w - (now_m - self._t_job0),
            "shm_token": self._shm_token,
            "topology": self.topology,
            "topo_gen": self.topo_gen,
            "max_brokers": self._max_brokers,
            "shards": [
                {"shard": bs.shard,
                 "addr": list(bs.addr) if bs.addr else None,
                 "pid": bs.pid, "spawns": bs.spawns}
                for bs in self.shards
            ],
            "slots": [
                {"worker": s.worker, "pid": s.pid,
                 "invocations": s.invocations, "terminal": s.terminal,
                 "inv_start": s.inv_start,
                 "spawned_wall": (
                     now_w - (now_m - s.spawned_at) if s.spawned_at else None
                 ),
                 "shm_segs": list(s.shm_segs),
                 "pre_pid": (
                     s.pre_proc.pid if s.pre_proc is not None else None
                 ),
                 "pre_shm_segs": list(s.pre_shm_segs),
                 "held": s.held}
                for s in self.slots
            ],
            "lifetimes": self.lifetimes,
            "evictions": self.evictions,
            "scale_events": self.scale_events,
            "respawns": self.respawns,
            "broker_respawns": self.broker_respawns,
            "cold_start_overlaps": self.cold_start_overlaps,
            "retired_shard_stats": self.retired_shard_stats,
            "topology_events": self.topology_events,
            "scripted_fired": self._scripted_fired,
            "chaos_fired": sorted(self._chaos_fired),
            "chaos_events": self.chaos_events,
            "resumed": self._resumed,
        }
        tmp = self._journal_path() + ".tmp"
        with open(tmp, "w") as f:
            json.dump(state, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, self._journal_path())

    def _resume_from_journal(self) -> bool:
        """Re-adopt a previous supervisor's pool from its journal.

        Live brokers/workers are adopted by pid (they kept running
        headless and never noticed the change of management); dead ones
        respawn through the normal WAL-replay / checkpoint-replay paths.
        Telemetry is re-polled from step 1 — the coordinator retains the
        full history, so the resumed history is identical."""
        path = self._journal_path()
        if not self._resume or not os.path.exists(path):
            return False
        with open(path) as f:
            st = json.load(f)
        now_m, now_w = time.monotonic(), time.time()
        self._t_job0 = now_m - (now_w - st["t_job0_wall"])
        self._shm_token = st["shm_token"]
        self.topology = st["topology"]
        self.topo_gen = st["topo_gen"]
        self._max_brokers = st["max_brokers"]
        self.lifetimes = st["lifetimes"]
        self.evictions = {int(k): v for k, v in st["evictions"].items()}
        self.scale_events = st["scale_events"]
        self.respawns = st["respawns"]
        self.broker_respawns = st["broker_respawns"]
        self.cold_start_overlaps = st["cold_start_overlaps"]
        self.retired_shard_stats = st["retired_shard_stats"]
        self.topology_events = st["topology_events"]
        self._scripted_fired = st["scripted_fired"]
        self._chaos_fired = set(st["chaos_fired"])
        self.chaos_events = st["chaos_events"]
        self._resumed = st.get("resumed", 0) + 1
        adopted_b = adopted_w = 0
        self.shards = []
        self._conns = []
        for js in st["shards"]:
            bs = _BrokerShard(shard=js["shard"], spawns=js["spawns"])
            bs.addr = tuple(js["addr"]) if js["addr"] else None
            if _pid_alive(js["pid"]):
                bs.adopted_pid = js["pid"]
                adopted_b += 1
            self.shards.append(bs)
            self._conns.append(None)
        for bs in self.shards:
            if not bs.alive:  # spawns > 0: the WAL replays before binding
                self._spawn_broker(bs)
        self.slots = []
        for js in st["slots"]:
            s = _Slot(worker=js["worker"], invocations=js["invocations"],
                      terminal=js["terminal"], inv_start=js["inv_start"],
                      held=js["held"])
            s.shm_segs = list(js["shm_segs"])
            if js["spawned_wall"]:
                s.spawned_at = now_m - (now_w - js["spawned_wall"])
            if js["terminal"] is None and _pid_alive(js["pid"]):
                s.adopted_pid = js["pid"]
                adopted_w += 1
            # a pre-warmed successor gated by the dead supervisor: its
            # gate can never open from here — kill it and bill the
            # (real, live-function) seconds it ran
            if js["pre_pid"] and _pid_alive(js["pre_pid"]):
                try:
                    os.kill(js["pre_pid"], signal.SIGKILL)
                except OSError:
                    pass
            self.slots.append(s)
        # non-terminal slots that died alongside the supervisor respawn
        # through the normal crash path (restore newest ckpt + replay)
        for s in self.slots:
            if s.terminal is None and not s.alive and not s.held:
                self.respawns.append(
                    {"worker": s.worker, "exit_code": None,
                     "restored_step": self._restored_step(s),
                     "at_frontier": self._frontier,
                     "resume_orphan": True}
                )
                self._spawn(s)
        # stamp recovery on the kill event that took the predecessor down
        for rec in self.chaos_events:
            if rec.get("kind") == "supervisor_kill" \
                    and "recovery_s" not in rec:
                rec["recovery_s"] = now_w - rec["killed_at_wall"]
                rec["readopted"] = {"workers": adopted_w,
                                    "brokers": adopted_b}
        # the coordinator retains full telemetry: re-poll from step 1
        self._poll_since = 1
        self._frontier = 0
        return True

    # -- main loop ------------------------------------------------------------

    def run(self) -> dict:
        cfg = self.cfg
        os.makedirs(cfg.run_dir, exist_ok=True)
        self._t_job0 = time.monotonic()
        dump = None
        try:
            if not self._resume_from_journal():
                self._start_brokers()
                for slot in self.slots:
                    self._spawn(slot)
            self._save_journal()
            deadline = self._t_job0 + cfg.deadline_s
            while True:
                time.sleep(cfg.poll_interval_s)
                self._reap_brokers()
                resp = self._poll()
                statuses = resp["statuses"]

                # seeded fault injection (runtime/faults.py): SIGKILLs,
                # WAL corruption, supervisor suicide — the chaos plane
                # compiled from cfg.chaos + the legacy kill_* knobs
                self._chaos_step()

                for slot in self.slots:
                    exited = (
                        slot.proc.poll() is not None
                        if slot.proc is not None
                        else slot.adopted_pid is not None
                        and not _pid_alive(slot.adopted_pid)
                    )
                    if slot.terminal is None and exited:
                        # refresh statuses so a just-sent bye is not
                        # misread as a crash
                        statuses = self._poll()["statuses"]
                        self._reap(slot, statuses)

                self._maybe_prespawn()
                self._scan_prewarm_ready()

                # topology handover (DESIGN.md §16): every live worker
                # parked at the fence -> migrate the store and resume
                if self._handover is not None and all(
                    s.terminal is not None or s.held for s in self.slots
                ):
                    self._complete_handover()

                all_alive = all(
                    s.alive for s in self.slots if s.terminal is None
                )
                if all_alive and self._handover is None:
                    if self._scripted_fired < len(cfg.scripted_evict_steps):
                        nxt = cfg.scripted_evict_steps[self._scripted_fired]
                        if self._frontier >= nxt:
                            if self._evict_victim("scripted"):
                                self._scripted_fired += 1
                    if self.tuner is not None and self.history:
                        decision = self.tuner.decide()
                        if decision.remove_worker:
                            self._evict_victim(
                                decision.reason, decision.s_delta
                            )
                    if self._retunes_pending:
                        nxt, changes = self._retunes_pending[0]
                        if self._frontier >= nxt:
                            # settled either way: a past-end refusal is
                            # permanent, retrying it would spin forever
                            self._initiate_retune(changes)
                            self._retunes_pending.pop(0)
                    elif self.topo_tuner is not None and self.history:
                        last = self.history[-1]
                        p = max(int(last.get("p_active") or 1), 1)
                        # per-worker bytes/step for the cost-model
                        # tie-break — must be current BEFORE next_action
                        # picks a winner
                        self.topo_tuner.bytes_per_step = (
                            float(last.get("wire_bytes") or 0.0) / p
                        )
                        self.topo_tuner.n_workers = p
                        action = self.topo_tuner.next_action()
                        if action is not None:
                            _kind, cell = action
                            if not self._initiate_retune(cell):
                                self.topo_tuner.abandon()

                self._save_journal()

                if all(s.terminal is not None for s in self.slots):
                    self._poll()
                    break
                if time.monotonic() > deadline:
                    raise RuntimeError(
                        f"FaaS job deadline ({cfg.deadline_s}s) exceeded at "
                        f"frontier {self._frontier}; statuses={statuses}; "
                        f"logs in {os.path.join(cfg.run_dir, 'logs')}"
                    )

            # a fault whose recovery the job's end beat to the punch
            for p in self._chaos_pending:
                p["rec"]["recovery_s"] = None
                self.chaos_events.append(p["rec"])
            self._chaos_pending = []
            if cfg.retain_updates:
                dump = self._dump_updates()
            self._stopping = True
            shard_stats = []
            for s in range(len(self.shards)):
                resp, _ = self._rpc({"t": "shutdown"}, shard=s)
                shard_stats.append(resp)
            # shards retired by a mid-job shrink already reported at
            # retirement; their socket stats belong in the same rollup
            shard_stats.extend(self.retired_shard_stats)
            # clean completion: the journal has nothing left to recover
            if self._journal_enabled:
                try:
                    os.unlink(self._journal_path())
                except OSError:
                    pass
        finally:
            for slot in self.slots:
                if slot.alive:
                    _sigkill(slot)
                if slot.pre_proc is not None and slot.pre_proc.poll() is None:
                    slot.pre_proc.kill()
            for conn in self._conns:
                if conn is not None:
                    conn.close()
            self._conns = [None] * len(self.shards)
            for bs in self.shards:
                if bs.proc is not None:
                    bs.proc.terminate()
                    try:
                        bs.proc.wait(timeout=5.0)
                    except subprocess.TimeoutExpired:
                        bs.proc.kill()
                elif bs.adopted_pid is not None and _pid_alive(bs.adopted_pid):
                    _terminate_pid(bs.adopted_pid)
            # the supervisor owns every shm segment: none may outlive the
            # job (they are named host-global resources, not fds)
            for seg in self._shm_segments.values():
                seg.unlink()
            self._shm_segments.clear()

        wall = time.monotonic() - self._t_job0
        # the topology bills what it runs: one Redis-analogue VM per shard
        # — the PEAK shard count under live re-sharding (a shard that ran
        # for part of the job still occupied a VM slot; honest upper bound)
        bill = faas_cost(self.lifetimes, wall, n_redis=self._max_brokers)
        return self._result(wall, bill, shard_stats, dump)

    # -- results --------------------------------------------------------------

    def _dump_updates(self) -> list[dict]:
        """Merge every shard's stored slices back into full update trees
        (``sharding.LeafBuffers`` reassembles split leaves too)."""
        import jax
        import numpy as np

        leaf_keys = protocol.tree_keys(self.wl.params0)
        treedef = jax.tree_util.tree_structure(self.wl.params0)
        from repro.runtime import sharding

        leaf_like = {
            k: (np.shape(leaf), np.asarray(leaf).dtype)
            for k, leaf in zip(
                leaf_keys, jax.tree_util.tree_leaves(self.wl.params0)
            )
        }
        acc: dict[tuple[int, int], sharding.LeafBuffers] = {}
        for s in range(len(self.shards)):
            resp, blob = self._rpc({"t": "dump"}, shard=s)
            for desc, m, leaf in sharding.iter_part_leaves(
                resp["parts"], blob
            ):
                key = (int(desc["worker"]), int(desc["step"]))
                if key not in acc:  # setdefault would zero-fill per leaf
                    acc[key] = sharding.LeafBuffers(leaf_like)
                acc[key].add(m, leaf)
        out = []
        for (worker, step) in sorted(acc):
            bufs = acc[(worker, step)]
            bufs.assert_complete(what=f"dump (worker {worker}, step {step})")
            out.append(
                {
                    "worker": worker,
                    "step": step,
                    "update": jax.tree_util.tree_unflatten(
                        treedef, [bufs[k] for k in leaf_keys]
                    ),
                }
            )
        return out

    def _final_eval(self) -> tuple[Optional[float], Optional[int]]:
        from repro.checkpoint import store as ckpt

        survivors = [s.worker for s in self.slots if s.terminal == "done"]
        if not survivors:
            return None, None
        w = min(survivors)
        d = os.path.join(self.cfg.run_dir, "ckpt", f"w{w:03d}")
        step = ckpt.latest_step(d)
        if step is None:
            return None, None
        import jax
        import jax.numpy as jnp

        from repro import optim as optim_lib

        optimizer = optim_lib.make(self.cfg.optimizer, self.cfg.lr)
        like = {
            "params": self.wl.params0,
            "opt": optimizer.init(self.wl.params0),
            "residual": jax.tree.map(jnp.zeros_like, self.wl.params0),
        }
        tree = ckpt.restore(d, step, like)
        return self.wl.eval_fn(tree["params"]), step

    def _result(self, wall, bill: FaaSBill, shard_stats, dump):
        final_eval, final_ckpt_step = self._final_eval()
        hist = self.history
        durs = [r["dur_s"] for r in hist if r.get("dur_s")]
        phases = [r["phase"] for r in hist if r.get("phase")]
        phase_s_mean = (
            {
                k: sum(p[k] for p in phases if p.get(k) is not None)
                / max(sum(1 for p in phases if p.get(k) is not None), 1)
                for k in phases[0]
            }
            if phases
            else {}
        )
        # aggregate per-message byte accounting across shards (the merged
        # view existing callers read), keep the per-shard split alongside
        stats: dict[str, dict[str, int]] = {}
        for resp in shard_stats:
            for kind, row in (resp.get("stats") or {}).items():
                agg = stats.setdefault(
                    kind, {"count": 0, "bytes_in": 0, "bytes_out": 0}
                )
                for k in agg:
                    agg[k] += row.get(k, 0)
        dup_mismatches = sum(
            int(r.get("dup_mismatches", 0)) for r in shard_stats
        )
        wal_quarantined = sum(
            int(r.get("wal_quarantined", 0)) for r in shard_stats
        )
        result = {
            "workload": self.wl.name,
            "n_workers": self.cfg.n_workers,
            # FINAL topology (== starting topology unless a live re-shard
            # happened; 'topology'/'topology_events' carry the full story)
            "n_brokers": self.topology["n_brokers"],
            "transport": self.topology["transport"],
            "topology": dict(self.topology),
            "topology_gen": self.topo_gen,
            "topology_events": self.topology_events,
            "topology_tuner": (
                None if self.topo_tuner is None else self.topo_tuner.summary()
            ),
            "steps": self._frontier,
            "final_pool": sum(1 for s in self.slots if s.terminal == "done"),
            "final_loss": hist[-1]["loss"] if hist else None,
            "final_eval": final_eval,
            "final_ckpt_step": final_ckpt_step,
            "history": hist,
            "measured_step_s": (sum(durs) / len(durs)) if durs else None,
            "phase_s_mean": phase_s_mean,
            "wire_scheme": self.cfg.wire_scheme,
            "wire_quant": self.cfg.wire_quant,
            "wire_impl": self.cfg.wire_impl,
            # what launch/hostperf.py actually applied (None when off, and
            # tcmalloc: None inside when the library is absent) — every
            # benchmark row states its own substrate
            "hostperf": self.hostperf_applied,
            "invariant_max_err": max(
                (r["inv_err"] for r in hist), default=0.0
            ),
            "wire_bytes_total": sum(r["wire_bytes"] for r in hist),
            "scale_events": self.scale_events,
            "respawns": self.respawns,
            "n_respawns": len(self.respawns),
            "broker_respawns": self.broker_respawns,
            # pre-warmed respawn telemetry (cfg.prewarm): measured seconds
            # of runtime/XLA init that overlapped the previous invocation
            "cold_start_overlaps": self.cold_start_overlaps,
            "n_invocations": len(self.lifetimes),
            "lifetimes_s": list(self.lifetimes),
            "dup_mismatches": dup_mismatches,
            # chaos plane (runtime/faults.py): what fired, how long each
            # recovery took, and what the WAL CRC check had to drop
            "chaos": None if self.plan is None else self.plan.to_spec(),
            "chaos_events": self.chaos_events,
            "wal_quarantined_bytes": wal_quarantined,
            "supervisor_resumed": self._resumed,
            "wall_s": wall,
            "bill": {
                "worker_seconds": bill.worker_seconds,
                "wall_seconds": bill.wall_seconds,
                "worker_cost": bill.worker_cost,
                "infra_cost": bill.infra_cost,
                "n_redis": bill.n_redis,
                "total": bill.total,
            },
            "broker_stats": stats,
            "broker_stats_per_shard": [
                r.get("stats") or {} for r in shard_stats
            ],
            # codec-accounted published-update bytes each shard measured —
            # the per-shard half of the §10 invariant (== what
            # runtime.sharding.predict_shard_nbytes accounts)
            "broker_update_bytes_per_shard": [
                int(r.get("update_bytes", 0)) for r in shard_stats
            ],
        }
        if dump is not None:
            result["updates"] = dump
        return result


def run_job(cfg: FaaSJobConfig) -> dict:
    """Run one FaaS training job to completion; returns the result dict."""
    return Supervisor(cfg).run()


def final_params_digest(cfg: FaaSJobConfig, worker: int = 0) -> str:
    """sha256 over one worker's final checkpointed parameters from a
    finished run — the bit-identity witness the transport/topology sweeps
    and the wire guard compare across ``{tcp, shm} x n_brokers``."""
    import hashlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import optim as optim_lib
    from repro.checkpoint import store as ckpt

    wl = workload_lib.build(cfg.workload, cfg.workload_cfg)
    optimizer = optim_lib.make(cfg.optimizer, cfg.lr)
    like = {
        "params": wl.params0,
        "opt": optimizer.init(wl.params0),
        "residual": jax.tree.map(jnp.zeros_like, wl.params0),
    }
    d = os.path.join(cfg.run_dir, "ckpt", f"w{worker:03d}")
    step = ckpt.latest_step(d)
    if step is None:
        raise FileNotFoundError(f"no final checkpoint under {d}")
    tree = ckpt.restore(d, step, like)
    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(tree["params"]):
        h.update(np.ascontiguousarray(np.asarray(leaf)).tobytes())
    return h.hexdigest()


# the canonical quickstart job — examples/mlless_faas.py runs it and
# benchmarks/fig6_autotuner.py calibrates the simulator against the SAME
# configuration, so it lives in exactly one place
PMF_QUICKSTART_CFG = {
    "n_users": 200,
    "n_movies": 300,
    "n_ratings": 12_000,
    "rank": 8,
    "batch_size": 512,
}


def pmf_quickstart_config(
    run_dir: str, n_workers: int = 4, total_steps: int = 140,
    n_brokers: int = 1, transport: str = "tcp",
    consistency: str = "isp", slack: int = 3,
    wire_impl: str = "numpy", hostperf: bool = False,
) -> FaaSJobConfig:
    """PMF on 4 CPU workers with a live knee-driven scale-in (~1 min)."""
    return FaaSJobConfig(
        run_dir=run_dir,
        workload="pmf",
        workload_cfg=dict(PMF_QUICKSTART_CFG),
        n_workers=n_workers,
        total_steps=total_steps,
        invocation_steps=max(total_steps // 2, 1),  # >= 2 real invocations
        checkpoint_every=20,
        optimizer="nesterov",
        # stale peer corrections shrink the stable step size (classic
        # delayed-gradient result): Nesterov at lr 0.3 rides the momentum
        # oscillation into NaN under slack once it reaches the curved
        # region near the optimum; 0.05 converges through the whole slack
        # range the CLI exposes — slower time-to-loss than ISP at 0.3,
        # which is the paper's fig9 point, measured live
        lr=0.3 if consistency == "isp" else 0.05,
        isp_v=0.7,
        n_brokers=n_brokers,
        transport=transport,
        consistency=consistency,
        slack=slack,
        wire_impl=wire_impl,
        hostperf=hostperf,
        autotune=True,
        tuner=AutoTunerConfig(
            sched_interval_s=0.5,
            delta_s=0.25,
            knee_slope_threshold=0.3,
            min_points_for_fit=8,
        ),
        deadline_s=480.0,
    )


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", default="pmf",
                    choices=workload_lib.WORKLOAD_NAMES)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--invocation-steps", type=int, default=1_000_000)
    ap.add_argument("--n-brokers", type=int, default=1)
    ap.add_argument("--transport", default="tcp", choices=("tcp", "shm"))
    ap.add_argument("--consistency", default="isp", choices=("isp", "ssp"))
    ap.add_argument("--slack", type=int, default=3)
    ap.add_argument("--autotune", action="store_true")
    ap.add_argument("--prewarm", action="store_true")
    ap.add_argument("--run-dir", default="/tmp/repro_faas")
    ap.add_argument("--out", default=None)
    ap.add_argument("--config", default=None,
                    help="JSON FaaSJobConfig (from_dict); overrides the "
                         "per-field job flags")
    ap.add_argument("--resume", action="store_true",
                    help="re-adopt a previous supervisor's pool from its "
                         "journal when one exists in run_dir")
    ap.add_argument("--allow-self-kill", action="store_true",
                    help="permit a supervisor_kill fault event (only safe "
                         "under an external driver that re-executes us)")
    args = ap.parse_args()
    if args.config:
        with open(args.config) as f:
            cfg = FaaSJobConfig.from_dict(json.load(f))
    else:
        cfg = FaaSJobConfig(
            run_dir=args.run_dir,
            workload=args.workload,
            n_workers=args.workers,
            total_steps=args.steps,
            invocation_steps=args.invocation_steps,
            n_brokers=args.n_brokers,
            transport=args.transport,
            consistency=args.consistency,
            slack=args.slack,
            autotune=args.autotune,
            prewarm=args.prewarm,
        )
    res = Supervisor(
        cfg, allow_self_kill=args.allow_self_kill, resume=args.resume
    ).run()
    slim = {k: v for k, v in res.items() if k not in ("history", "updates")}
    print(json.dumps(slim, indent=1, default=str))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(res, f, indent=1, default=str)


if __name__ == "__main__":
    main()
