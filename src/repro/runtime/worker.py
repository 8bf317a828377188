"""Stateless FaaS worker — one invocation of the MLLess training function.

Spawned as ``python -m repro.runtime.worker --brokers HOST:PORT[,HOST:PORT...]
--worker-id K`` with *no other job state on the command line*: everything
(workload name + config, ISP threshold, step budget, checkpoint root) comes
from the coordinator shard's hello response, and model/optimizer/residual
state is restored from ``checkpoint.store`` — the invocation-bounded,
externally-checkpointed worker model of the paper (§5).

The update store is sharded by leaf key over the N broker shards
(``runtime.sharding``, DESIGN.md §11): the worker holds ONE persistent
``wire.Transport`` channel per shard, publishes each shard its slice of
every update, and pulls each shard's coalesced slice of the peers'
updates — shard 0 (the coordinator) additionally serves minibatch keys,
membership, and telemetry.  The channel is pluggable (DESIGN.md §12):
``--transport tcp`` (default) is the persistent loopback socket;
``--transport shm`` rides the supervisor-allocated shared-memory ring
segments (``--shm-seg`` base name, one segment per shard) — same
framing, same codec, same accounted bytes, no kernel socket copy.

Per step t the worker runs the *paper-faithful replica semantics* of
``core.isp`` (the same math ``core.simulator`` vmaps, here on a real
process):

1. fetch its minibatch key (piggybacked on the previous coordinator pull;
   a ``batch`` round trip only on the first step of an invocation) and
   load the batch locally;
2. ``u_t = optimizer(grads) / P_active(t)`` (averaged-gradient scaling);
3. ``sig, residual' = filter_update(residual + u_t)`` — the ISP
   significance split of ``core.isp``, bit-identical semantics;
4. publish ``sig`` sliced per shard through the shared wire codec
   (``repro.wire``; scheme and optional fp16/bf16 value quantization from
   the job config, any quantization error fed back into the residual);
5. pull the peers' significant updates for t (ISP barrier per shard, ONE
   coalesced round trip per shard on its persistent connection) and apply
   ``x += u_t + sum_peers sig`` — own update in full, peers filtered.
   Each leaf is owned by exactly one shard and peers arrive in ascending
   worker order within a shard, so the per-leaf float32 summation order
   is fixed regardless of the shard count — final params are bit-exact
   across ``n_brokers`` (asserted by ``tests/test_runtime_sharded.py``);
6. on an eviction notice effective at t: publish ``x + residual`` as the
   flush payload (the leaving worker's model-averaging hand-off, sliced
   per shard) and exit; on a flush from a leaving peer: mean-preserving
   reintegration via ``dist.elastic.reintegrate_into``.

Every step reports a per-phase wall-clock breakdown (fetch / compute /
encode / wire / decode) so data-path regressions are attributable
(surfaced in ``BENCH_runtime.json``).

Crash recovery is replay: a respawned worker restores the newest checkpoint
and re-executes forward — every input (minibatch key, peer updates, pool
membership) is served deterministically by the brokers, so replayed
publishes are bit-identical (each shard counts any mismatch) and the pool
never observes a diverging history.  A *broker shard* crash is equally
survivable: the RPC layer retries through the supervisor's respawn window,
and the respawned shard replays its write-ahead log, so any acked publish
is still there and any retried one dup-checks bit-identical.

Exit codes: 0 clean (done / evicted / invocation boundary), 3 broker
abort, 4 broker unreachable, 5 barrier deadline exceeded.
"""

from __future__ import annotations

import argparse
import contextlib
import errno
import os
import threading
import time
from typing import Any, Optional

from repro.runtime.faults import FaultPlan, RetryPolicy, WorkerFaults

PyTree = Any


def _make_rpc(conn, policy_fn):
    """Retrying RPC over one persistent broker-shard connection.

    The retry window (``RetryPolicy``, DESIGN.md §17.4) must comfortably
    cover a supervisor shard respawn (detect + python start + WAL replay
    + bind), which a worker rides out instead of dying into a full
    checkpoint-replay cold start.  ``policy_fn`` is late-bound: the
    job-configured policy only arrives with the hello response.
    """

    def _rpc(header, payload=b"", timeout=None):
        policy: RetryPolicy = policy_fn()
        last: Optional[Exception] = None
        for _ in policy.attempts():
            try:
                return conn.request(
                    header, payload,
                    timeout=timeout if timeout is not None
                    else policy.timeout_s,
                )
            except (ConnectionError, OSError, TimeoutError) as e:
                last = e
        raise SystemExit(4) from last

    return _rpc


class _Membership:
    """Worker-side view of the eviction table (worker -> effective step).

    Updated from every shard response; entries are only ever added (the
    coordinator is the single minting authority), so merging views from
    shards with differently-stale tables is safe.
    """

    def __init__(self, n_workers: int):
        self.P = n_workers
        self.evictions: dict[int, int] = {}
        # topology epoch fence (DESIGN.md §16): once the coordinator mints
        # it, every worker exits at loop-top t >= fence so the supervisor
        # can re-shard the store at an invocation boundary
        self.topo_fence: Optional[int] = None

    def update(self, resp: dict) -> None:
        for k, v in (resp.get("evictions") or {}).items():
            self.evictions[int(k)] = int(v)
        if resp.get("topo_fence") is not None:
            self.topo_fence = int(resp["topo_fence"])

    def p_active(self, step: int) -> int:
        return self.P - sum(1 for e in self.evictions.values() if e <= step)

    def my_evict_step(self, worker: int) -> Optional[int]:
        return self.evictions.get(worker)


def run_worker(
    addrs: list[tuple[str, int]],
    worker_id: int,
    transport: str = "tcp",
    shm_seg: Optional[str] = None,
    job_id: Optional[str] = None,
    stop_event: Optional["threading.Event"] = None,
    compute_lock: Optional["threading.Lock"] = None,
    prewarm_gate: Optional[str] = None,
    _ready_cb=None,
) -> int:
    """One worker's life for one job.

    Solo (``job_id is None``) this is the single-job path, byte-identical
    to the pre-fleet build: no ``job`` header on any RPC, no key prefix.
    Under the multi-job control plane (DESIGN.md §14) one *process* runs
    one ``run_worker`` thread per admitted job: ``job_id`` tags every RPC
    (the broker routes it to that job's core) and prefixes every leaf key
    (``sharding.job_namespace``); ``compute_lock`` serializes the compute
    phases so one job's barrier stall is absorbed by another job's
    compute inside the same invocation (the bin-packing claim);
    ``stop_event`` is the shared invocation boundary — the first thread
    to hit its step budget sets it and every sibling winds down at its
    next barrier slice, so the process exits as one billable unit.

    ``prewarm_gate`` is the pre-warmed respawn path: connect, fetch the
    job config with a status-neutral warm hello, build + JIT-warm the
    step functions, signal readiness (``<gate>.ready``), then block until
    the supervisor opens the gate file — only THEN restore the newest
    checkpoint and run, so runtime/XLA init overlaps the tail of the
    previous invocation without ever racing its checkpoints.
    """
    # jax and friends are imported lazily so ``--help`` stays instant — the
    # import cost is the measured FaaS cold-start of each invocation.
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import optim
    from repro.checkpoint import store as ckpt
    from repro.core import isp as isp_lib
    from repro.dist.elastic import reintegrate_into
    from repro.launch.compile_cache import use_compile_cache
    from repro.runtime import protocol, sharding
    from repro.runtime import workload as workload_lib

    # ONE persistent channel per broker shard for the whole invocation —
    # the coalesced data path (DESIGN.md §10.3) instead of a TCP connect
    # per message.  conns[0] is the coordinator.  The transport factory
    # (wire.framing.make_transport) is the ONLY transport-aware line.
    # the bootstrap policy covers the hello round trip; the job-configured
    # one (FaaSJobConfig.rpc) replaces it as soon as the hello response
    # carries the job dict — per-worker reseed decorrelates the jitter
    # streams of concurrent retry loops without losing determinism
    rpc_policy = RetryPolicy().reseed(worker_id)

    def _policy() -> RetryPolicy:
        return rpc_policy

    n_shards = len(addrs)
    conns = [
        protocol.make_transport(
            transport,
            addr=a,
            shm_name=f"{shm_seg}s{s}" if shm_seg else None,
            timeout=rpc_policy.timeout_s,
        )
        for s, a in enumerate(addrs)
    ]
    # single-shard round trips (hello/batch/report/bye) go to the
    # coordinator; everything per-shard goes through the pipelined fanout
    rpc0 = _make_rpc(conns[0], _policy)

    def fanout(shard_ids, headers, payloads=None, timeout=None):
        """Pipelined RPC to several shards (send all, then collect all) —
        per-shard latencies overlap instead of summing, which is what
        makes the sharded store cheaper, not dearer, per barrier.  Retries
        whole rounds through a broker-shard respawn window; every op is
        idempotent so a replayed round is safe."""
        policy = _policy()
        payloads = payloads or [b""] * len(shard_ids)
        last: Optional[Exception] = None
        for _ in policy.attempts():
            try:
                return protocol.pipelined(
                    [conns[s] for s in shard_ids],
                    list(zip(headers, payloads)),
                    timeout=timeout if timeout is not None
                    else policy.timeout_s,
                )
            except (ConnectionError, OSError, TimeoutError) as e:
                last = e
        raise SystemExit(4) from last

    # fleet mode: tag every RPC with the job id (broker-side core routing)
    # and prefix every leaf key (store/WAL namespace).  Solo mode adds
    # NOTHING — headers and keys stay byte-identical to the pre-fleet
    # build, which is what the wire-guard byte gate pins.
    jtag = {} if job_id is None else {"job": str(job_id)}
    ns = sharding.job_namespace(job_id)
    # a warm hello (prewarm path) fetches the job config without touching
    # the worker's status — the previous invocation still owns it
    hello, _ = rpc0(
        {"t": "hello", "worker": worker_id, **jtag,
         **({"warm": True} if prewarm_gate is not None else {})}
    )
    job = hello["job"]
    members = _Membership(int(job["n_workers"]))
    members.update(hello)
    if job.get("rpc"):
        rpc_policy = RetryPolicy.from_dict(job["rpc"]).reseed(worker_id)
    # chaos plane (runtime/faults.py, DESIGN.md §17): this worker's slice
    # of the job's seeded fault plan — wire delays / stalls / resets,
    # checkpoint write failures, straggler compute delays.  With no plan
    # (the default) nothing installs and every hook stays dormant.
    _plan = FaultPlan.from_spec(job.get("chaos"))
    wfaults = WorkerFaults(_plan, worker_id) if _plan is not None else None
    if wfaults is not None:
        wfaults.install()

    # persistent jit cache: later invocations (respawns, invocation
    # boundaries, every worker after the first, later jobs) load compiled
    # step functions instead of re-paying the ~1 s XLA cold start — the
    # standard warm-container trick for FaaS runtimes.  Small step
    # functions compile in well under the default 1 s threshold, so every
    # compile is cached
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    wl = workload_lib.build(job["workload"], job["workload_cfg"])
    optimizer = optim.make(job["optimizer"], job["lr"])
    isp = isp_lib.ISPConfig(
        v=float(job["isp_v"]), decay=bool(job.get("isp_decay", True))
    )
    total_steps = int(job["total_steps"])
    invocation_steps = int(job.get("invocation_steps", 1_000_000))
    checkpoint_every = int(job.get("checkpoint_every", 10))
    pull_deadline_s = float(job.get("pull_deadline_s", 120.0))
    wire_scheme = str(job.get("wire_scheme", "auto"))
    wire_quant = str(job.get("wire_quant", "none"))
    wire_impl = str(job.get("wire_impl", "numpy"))
    # bounded-staleness mode (DESIGN.md §13): under 'ssp' a pull at step t
    # is served exactly the peers' updates of step t - slack - 1, so the
    # worker runs up to slack + 1 steps ahead of the slowest peer instead
    # of barriering every step; 'isp' (default) is unchanged
    consistency = str(job.get("consistency", "isp"))
    slack = int(job.get("slack", 3))
    ckpt_dir = os.path.join(job["run_dir"], "ckpt", f"w{worker_id:03d}")

    params = wl.params0
    opt_state = optimizer.init(params)
    residual = jax.tree.map(jnp.zeros_like, params)

    # the leaf-key -> shard partition: a pure function of the parameter
    # template, the shard count and the (topology-independent) leaf-split
    # threshold, so every worker, the supervisor, and the tests compute
    # the identical assignment (runtime.sharding)
    split_bytes = int(job.get("shard_split_bytes", 0))
    partitioner = str(job.get("partitioner", "greedy"))
    leaf_keys = protocol.tree_keys(params)
    assignment = sharding.tree_assignment(
        params, n_shards, split_bytes=split_bytes, namespace=ns,
        partitioner=partitioner,
    )
    leaves0 = [np.asarray(x) for x in jax.tree_util.tree_leaves(params)]
    treedef0 = jax.tree_util.tree_structure(params)
    # decode accumulators are keyed by the (namespaced) wire keys the
    # shard metas carry — one job can never decode into another's buffers
    leaf_like = {
        ns + k: (leaf.shape, leaf.dtype) for k, leaf in zip(leaf_keys, leaves0)
    }

    start_step = 1
    last_saved = 0

    def restore_latest() -> None:
        """Resume from the newest checkpoint whose content digest
        verifies, falling back generation by generation past corrupt
        ones (DESIGN.md §17.3) — deferred past the prewarm gate: a
        pre-warmed process must not read checkpoints the previous
        invocation is still writing."""
        nonlocal params, opt_state, residual, start_step, last_saved
        latest, tree = ckpt.restore_latest_valid(
            ckpt_dir,
            {"params": params, "opt": opt_state, "residual": residual},
        )
        if latest is not None:
            params, opt_state, residual = (
                tree["params"], tree["opt"], tree["residual"],
            )
            start_step = latest + 1
            last_saved = latest

    def compute(params, opt_state, residual, batch, inv_p, t):
        loss, grads = wl.grad_fn(params, batch)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        u = jax.tree.map(lambda a: (a * inv_p).astype(a.dtype), updates)
        sig, new_state, masks = isp_lib.filter_update(
            isp, isp_lib.ISPState(residual=residual, step=t), u, params
        )
        res = new_state.residual
        sent = isp_lib.communicated_fraction(masks)
        # conservation witness: sent + residual' - (residual + update), the
        # pool-wide ISP invariant the fault-injection test asserts on
        errs = jax.tree.map(
            lambda s, r2, r0, uu: jnp.max(jnp.abs((s + r2) - (r0 + uu))),
            sig, res, residual, u,
        )
        inv_err = jax.tree.reduce(jnp.maximum, errs)
        return u, sig, res, opt_state, loss, sent, inv_err

    compute = jax.jit(compute)
    apply_visible = jax.jit(
        lambda p, u, peers: jax.tree.map(
            lambda a, b, c: a + b + c.astype(a.dtype), p, u, peers
        )
    )
    reintegrate = jax.jit(reintegrate_into)
    # catch-up merge: peers-only apply (no own update) for the SSP drain
    apply_peers = jax.jit(
        lambda p, peers: jax.tree.map(
            lambda a, c: a + c.astype(a.dtype), p, peers
        )
    )

    def save_ckpt(step_done: int) -> None:
        nonlocal last_saved
        if step_done <= 0 or step_done == last_saved:
            return
        if wfaults is not None and wfaults.ckpt_should_fail(step_done):
            # simulated ENOSPC at the worst moment: after the staged npz
            # is written, before the atomic install — the store's staging
            # contract keeps the partial snapshot invisible
            def _enospc(tmp: str) -> None:
                raise OSError(errno.ENOSPC, "chaos: injected ENOSPC", tmp)

            ckpt.install_write_fault_hook(_enospc)
        try:
            ckpt.save(
                ckpt_dir,
                step_done,
                {"params": params, "opt": opt_state, "residual": residual},
                extra={"worker": worker_id, "next_step": step_done + 1},
            )
        except OSError as e:
            # a failed checkpoint write is survivable: the previous
            # generation stays restorable and replay covers the gap —
            # warn and train on rather than crash the invocation
            print(f"worker {worker_id}: checkpoint save at step "
                  f"{step_done} failed ({e}); continuing on the previous "
                  f"generation", flush=True)
            return
        finally:
            ckpt.clear_write_fault_hook()
        last_saved = step_done

    def bye(reason: str) -> None:
        if wfaults is not None:
            wfaults.uninstall()  # the farewell RPCs run fault-free
        rpc0({"t": "bye", "worker": worker_id, "reason": reason, **jtag})
        for c in conns:
            c.close()

    def pull_all(step: int):
        """One barrier's worth of pipelined coalesced pulls (all shards'
        long polls run server-side concurrently).  Returns (exit_code,
        shard_parts): code is None on success, 3 on broker abort, 5 on
        deadline, 7 when a sibling job thread declared the invocation
        boundary mid-barrier (fleet mode; checked between 2 s poll
        slices, never mid-RPC)."""
        nonlocal key_next
        deadline = time.monotonic() + pull_deadline_s
        shard_parts: list[Optional[tuple[list, bytes]]] = [None] * n_shards
        pending = list(range(n_shards))
        while pending:
            if stop_event is not None and stop_event.is_set():
                return 7, None
            # the 2 s timeout_s is protocol, not retry policy: the server
            # parks the long poll for one slice and answers not-ready, so
            # the client-side attempt bound is the policy's timeout_s
            resps = fanout(
                pending,
                [{"t": "pull", "worker": worker_id, "step": step,
                  "timeout_s": 2.0, **jtag} for _ in pending],
            )
            nxt = []
            for s, (resp, blob) in zip(pending, resps):
                if resp.get("abort"):
                    return 3, None
                members.update(resp)
                if resp.get("ready"):
                    if s == 0:
                        key_next = resp.get("key_next")
                    shard_parts[s] = (resp["parts"], blob)
                else:
                    nxt.append(s)
            pending = nxt
            if pending and time.monotonic() > deadline:
                return 5, None
        return None, shard_parts

    def decode_parts(shard_parts):
        """Peers' update slices + eviction-flush slices back into per-leaf
        accumulators (sharding.LeafBuffers handles split leaves).  Every
        element lives on exactly one shard and peers arrive in ascending
        worker order there, so the per-element float32 summation order is
        fixed for ANY shard count — the replay path and every peer stay
        bit-identical."""
        sums = sharding.LeafBuffers(leaf_like)
        flush_acc: dict[int, sharding.LeafBuffers] = {}
        for descs, blob in shard_parts:
            for desc, m, view in sharding.iter_part_views(descs, blob):
                if desc.get("flush"):
                    q = int(desc["worker"])
                    if q not in flush_acc:  # setdefault would zero-fill
                        flush_acc[q] = sharding.LeafBuffers(leaf_like)
                    flush_acc[q].add_encoded(m, view, impl=wire_impl)
                else:
                    sums.add_encoded(m, view, impl=wire_impl)
        peers_sum = jax.tree_util.tree_unflatten(
            treedef0, [sums[ns + k] for k in leaf_keys]
        )
        flushes = []
        for q, acc in flush_acc.items():
            # a flush is a full replica: reintegrating one with a missing
            # shard slice would silently fold zeros into every survivor
            acc.assert_complete(what=f"flush from worker {q}")
            flushes.append(
                (q, jax.tree_util.tree_unflatten(
                    treedef0, [acc[ns + k] for k in leaf_keys]
                ))
            )
        return peers_sum, flushes

    def apply_flushes(params, flushes, deliver_step: int):
        """Mean-preserving reintegration of leaving peers' replicas, in
        ascending worker order, divided by the pool size just before the
        step the flush is effective at (= delivered at, on both models)."""
        pool_before = members.p_active(deliver_step - 1)
        for _q, flushed in sorted(flushes, key=lambda kv: kv[0]):
            params = reintegrate(
                params, flushed, jnp.asarray(pool_before, jnp.float32)
            )
        return params

    def ssp_drain(params):
        """Catch-up merge: the last regular pull (step T) delivered the
        frontier T - slack - 1, so the retained steps T - slack .. T are
        still undelivered.  Pull them via the same schedule (a pull at td
        delivers td - slack - 1) and apply peers-only, step-ascending —
        the same per-leaf order a peer that saw them live used.  Returns
        (exit_code, params); the caller checkpoints at the sentinel step
        total_steps + 1 afterwards so a respawn never drains twice."""
        for td in range(total_steps + 1, total_steps + slack + 2):
            code, shard_parts = pull_all(td)
            if code is not None:
                return code, params
            peers_sum, flushes = decode_parts(shard_parts)
            params = apply_peers(params, peers_sum)
            if flushes:
                params = apply_flushes(params, flushes, td - slack - 1)
        return None, jax.block_until_ready(params)

    if prewarm_gate is not None:
        # pre-warmed respawn (DESIGN.md §14.5): pay the jax import, XLA
        # backend init and step-function compile NOW, overlapping the
        # tail of the previous invocation, then hold at the gate.  The
        # warm-up runs on the initial template (identical shapes/dtypes
        # to the live state) and discards its outputs — no job state is
        # touched before the gate opens.
        warm_batch = wl.batch(0)
        jax.block_until_ready(
            compute(
                params, opt_state, residual, warm_batch,
                jnp.asarray(1.0, jnp.float32), jnp.asarray(1, jnp.int32),
            )
        )
        zeros_p = jax.tree.map(jnp.zeros_like, params)
        zeros_f = jax.tree.map(
            lambda a: jnp.zeros(a.shape, jnp.float32), params
        )
        jax.block_until_ready(apply_visible(params, zeros_p, zeros_f))
        if _ready_cb is not None:
            _ready_cb()  # fleet: last job thread signals for the process
        else:
            with open(prewarm_gate + ".ready", "w"):
                pass
        while not os.path.exists(prewarm_gate):
            if stop_event is not None and stop_event.is_set():
                for c in conns:
                    c.close()
                return 0
            time.sleep(0.02)
        # NOW this invocation owns the worker slot: announce for real
        hello2, _ = rpc0({"t": "hello", "worker": worker_id, **jtag})
        members.update(hello2)
    restore_latest()

    t = start_step
    steps_this_invocation = 0
    key_next: Optional[int] = None  # piggybacked by the previous pull
    while True:
        if wfaults is not None:
            wfaults.at_step(t)  # arm this step's wire/checkpoint events
        ev = members.my_evict_step(worker_id)
        # an eviction effective past the job's end is a no-op (the broker
        # refuses to grant those, but guard anyway): finish as 'done'
        if ev is not None and ev <= total_steps and t >= ev:
            # eviction effective at step ev: publish replica + residual (the
            # paper's leaving-worker hand-off, error-feedback form: no
            # accumulated update mass is lost) and end this worker's life.
            # Flushes are full replicas, so the scheme stays 'auto' (dense
            # wins); under --wire-quant the VALUES ride the job's fp16/bf16
            # quantizer — an explicit opt-in to a lossy hand-off that
            # halves the largest single messages in the system (the
            # survivors' mean-preserving pull folds the quantized replica
            # exactly as published, so replay stays bit-identical).  The
            # default 'none' ships the exact dense bytes of the pre-fleet
            # build.
            flushed = jax.tree.map(lambda x, r: x + r, params, residual)
            per_shard, _ = sharding.encode_tree_sharded(
                flushed, assignment, n_shards,
                quant=wire_quant, impl=wire_impl,
                split_bytes=split_bytes, namespace=ns,
            )
            fanout(
                list(range(n_shards)),
                [{"t": "flush", "worker": worker_id, "step": ev,
                  "meta": meta, **jtag} for meta, _ in per_shard],
                [parts for _, parts in per_shard],
            )
            bye("evicted")
            return 0
        # topology epoch fence (DESIGN.md §16): exit cleanly BEFORE
        # starting step fence so the supervisor can migrate the store.
        # After an eviction check on purpose — a granted eviction step is
        # always < fence (the mint guarantees it), so a leaver's flush
        # still lands in a barrier the survivors complete pre-fence.  The
        # checkpoint at fence-1 is durable before the handover starts, so
        # the respawned invocation resumes AT the fence and never replays
        # a pre-fence step against the re-sharded store.
        fence = members.topo_fence
        if fence is not None and t >= fence:
            save_ckpt(t - 1)
            bye("topo-fence")
            return 0
        if t > total_steps:
            if consistency == "ssp" and t == total_steps + 1:
                # drain exactly once: the sentinel checkpoint below makes
                # a post-drain respawn resume at t = total_steps + 2 and
                # skip straight to bye; a mid-drain SIGKILL restores a
                # step <= total_steps, replays (publishes dup-check
                # bit-identical), and drains again from scratch
                code, params = ssp_drain(params)
                if code == 7:
                    # invocation boundary mid-drain: do NOT checkpoint the
                    # partially drained params — the respawn restores a
                    # pre-drain step and re-drains from scratch (pulls are
                    # read-only, so the replay is exact)
                    bye("invocation-end")
                    return 0
                if code is not None:
                    return code
                save_ckpt(total_steps + 1)
            else:
                save_ckpt(t - 1)
            bye("done")
            return 0
        if steps_this_invocation >= invocation_steps or (
            stop_event is not None and stop_event.is_set()
        ):
            if stop_event is not None:
                # first thread to hit its budget declares the boundary for
                # the whole process — sibling jobs wind down at their next
                # barrier slice, and the supervisor respawns ONE invocation
                stop_event.set()
            save_ckpt(t - 1)
            bye("invocation-end")
            return 0

        tp = time.perf_counter
        t0 = tp()
        # -- fetch: minibatch key (piggybacked except on the first step of
        #    an invocation) + local batch materialization
        if key_next is None:
            resp, _ = rpc0(
                {"t": "batch", "worker": worker_id, "step": t, **jtag}
            )
            members.update(resp)
            key = int(resp["key"])
        else:
            key = key_next
        batch = wl.batch(key)
        t_fetch = tp()
        # -- compute: grads -> optimizer -> ISP split (block for honest
        #    phase attribution; jax dispatch is asynchronous).  In fleet
        #    mode the process-wide lock serializes sibling jobs' compute
        #    phases — the invocation models one billable vCPU, and a job
        #    only computes while its siblings are stalled on barriers
        #    (the bin-packing the cost rollup prices)
        p_act = members.p_active(t)
        with compute_lock if compute_lock is not None else (
            contextlib.nullcontext()
        ):
            u, sig, res, opt_state, loss, sent, inv_err = (
                jax.block_until_ready(
                    compute(
                        params,
                        opt_state,
                        residual,
                        batch,
                        jnp.asarray(1.0 / p_act, jnp.float32),
                        jnp.asarray(t, jnp.int32),
                    )
                )
            )
        if wfaults is not None:
            # injected straggler stall (compute_delay events — what the
            # old ad-hoc ``straggler`` knob compiled into), counted into
            # this worker's measured compute phase: the peers' barrier
            # exposure to it is what the consistency models price
            # differently
            delay = wfaults.compute_delay_s(t)
            if delay > 0.0:
                time.sleep(delay)
        t_compute = tp()
        # -- encode: shared wire codec, sliced per shard; quantization
        #    error (if any) is error-feedback — it joins the residual,
        #    conserving update mass
        per_shard, qerr = sharding.encode_tree_sharded(
            sig, assignment, n_shards,
            scheme=wire_scheme, quant=wire_quant,
            with_residual=(wire_quant != "none"),
            split_bytes=split_bytes, namespace=ns, impl=wire_impl,
        )
        if qerr is not None:
            # fence the async residual fold: without it the tree.map's
            # device work smears into whatever phase blocks next, and
            # t_encode under-reports the encode phase it belongs to
            res = jax.block_until_ready(jax.tree.map(
                lambda r, e: r + e.astype(r.dtype), res, qerr
            ))
        total_bytes = sum(
            protocol.wire_bytes(meta) for meta, _ in per_shard
        )
        t_encode = tp()
        # -- wire: one pipelined publish round (every shard gets its slice;
        #    the coordinator's carries the telemetry header), then
        #    pipelined coalesced pulls — all shards' ISP-barrier long
        #    polls run server-side concurrently
        pub_hdrs = []
        for s, (meta, _parts) in enumerate(per_shard):
            hdr = {"t": "publish", "worker": worker_id, "step": t,
                   "meta": meta, **jtag}
            if s == 0:
                hdr.update(
                    loss=float(loss),
                    sent_fraction=float(sent),
                    inv_err=float(inv_err),
                    wire_bytes=total_bytes,
                )
            pub_hdrs.append(hdr)
        for ack, _ in fanout(
            list(range(n_shards)), pub_hdrs,
            [parts for _, parts in per_shard],
        ):
            members.update(ack)

        code, shard_parts = pull_all(t)
        if code == 7:
            # sibling-declared invocation boundary mid-barrier: step t's
            # publish is durable but its pull never completed, and
            # opt_state is already advanced locally — exit WITHOUT a
            # checkpoint, so the respawn restores the last consistent
            # step and replays forward (publishes dup-check bit-identical)
            bye("invocation-end")
            return 0
        if code is not None:
            return code
        t_wire = tp()
        # -- decode: under 'isp' the parts are the peers' step-t slices;
        #    under 'ssp' the frontier step t - slack - 1's (empty while
        #    that is < 1) — same codec, same fixed per-leaf order
        peers_sum, flushes = decode_parts(shard_parts)
        t_decode = tp()
        # -- apply (counted as compute): own update + the delivered peers
        #    + reintegration of any flush effective at the delivered step
        params = apply_visible(params, u, peers_sum)
        if flushes:
            deliver_step = t - slack - 1 if consistency == "ssp" else t
            params = apply_flushes(params, flushes, deliver_step)
        params = jax.block_until_ready(params)
        residual = res
        t_apply = tp()
        rpc0(
            {
                "t": "report", "worker": worker_id, "step": t, **jtag,
                "dur_s": float(t_apply - t0),
                "phase": {
                    "fetch": t_fetch - t0,
                    "compute": (t_compute - t_fetch) + (t_apply - t_decode),
                    "encode": t_encode - t_compute,
                    "wire": t_wire - t_encode,
                    "decode": t_decode - t_wire,
                },
            },
        )
        steps_this_invocation += 1
        if t % checkpoint_every == 0:
            save_ckpt(t)
        t += 1


def run_worker_fleet(
    addrs: list[tuple[str, int]],
    worker_id: int,
    job_ids: list[str],
    transport: str = "tcp",
    shm_seg: Optional[str] = None,
    prewarm_gate: Optional[str] = None,
) -> int:
    """One invocation hosting several jobs: one ``run_worker`` thread per
    job, bin-packed onto one billable process (DESIGN.md §14.3).

    Each thread owns its own per-shard connections (the framed transports
    are not thread-safe) — under shm each job rides its own segment family
    ``<base>g<job>s<shard>``.  A shared stop event makes the invocation
    boundary process-wide, and a shared compute lock serializes the
    compute phases so one job computes exactly while its siblings stall
    on barriers.  A thread that crashes (nonzero code) also declares the
    boundary: sibling jobs wind down cleanly as ``bye:invocation-end``
    while the crashed job's status stays ``running``, which is precisely
    the signal the scheduler's reaper classifies per job.  Exit code is
    the max across threads (0 when every job ended cleanly).
    """
    stop_event = threading.Event()
    compute_lock = threading.Lock()
    codes: dict[str, int] = {}
    ready_lock = threading.Lock()
    ready_n = [0]

    def _ready() -> None:
        # the process is warm only once EVERY job's step functions are:
        # the last thread through signals the supervisor
        with ready_lock:
            ready_n[0] += 1
            if ready_n[0] == len(job_ids) and prewarm_gate is not None:
                with open(prewarm_gate + ".ready", "w"):
                    pass

    def _one(jid: str) -> None:
        seg = f"{shm_seg}g{jid}" if shm_seg else None
        try:
            code = run_worker(
                addrs, worker_id, transport=transport, shm_seg=seg,
                job_id=jid, stop_event=stop_event,
                compute_lock=compute_lock, prewarm_gate=prewarm_gate,
                _ready_cb=_ready if prewarm_gate is not None else None,
            )
        except SystemExit as e:
            code = int(e.code or 0)
        except BaseException:
            code = 1
        codes[jid] = code
        if code != 0:
            stop_event.set()

    threads = [
        threading.Thread(target=_one, args=(jid,), name=f"job-{jid}")
        for jid in job_ids
    ]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    return max(codes.values()) if codes else 1


def _parse_addrs(spec: str) -> list[tuple[str, int]]:
    out = []
    for item in spec.split(","):
        host, port = item.strip().rsplit(":", 1)
        out.append((host, int(port)))
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--brokers", default=None,
                    help="comma-separated HOST:PORT per shard "
                    "(shard 0 = coordinator)")
    ap.add_argument("--broker", default=None,
                    help="single-shard HOST:PORT (legacy alias)")
    ap.add_argument("--worker-id", type=int, required=True)
    ap.add_argument("--transport", default="tcp", choices=("tcp", "shm"),
                    help="update-path channel per shard (wire.framing."
                    "make_transport); shm needs --shm-seg")
    ap.add_argument("--shm-seg", default=None,
                    help="shared-memory segment base name (supervisor-"
                    "allocated); shard s attaches '<base>s<s>' (fleet "
                    "mode: '<base>g<job>s<s>')")
    ap.add_argument("--jobs", default=None,
                    help="comma-separated job ids — run one training "
                    "thread per job, bin-packed onto this one invocation "
                    "(fleet mode; every RPC is job-tagged)")
    ap.add_argument("--prewarm-gate", default=None,
                    help="pre-warmed respawn: JIT-warm, touch "
                    "'<gate>.ready', then hold until the gate file "
                    "appears before restoring state and training")
    args = ap.parse_args()
    spec = args.brokers or args.broker
    if not spec:
        ap.error("--brokers (or --broker) is required")
    if args.transport == "shm" and not args.shm_seg:
        ap.error("--transport shm requires --shm-seg")
    addrs = _parse_addrs(spec)
    if args.jobs:
        raise SystemExit(
            run_worker_fleet(
                addrs,
                args.worker_id,
                [j.strip() for j in args.jobs.split(",") if j.strip()],
                transport=args.transport,
                shm_seg=args.shm_seg,
                prewarm_gate=args.prewarm_gate,
            )
        )
    raise SystemExit(
        run_worker(
            addrs,
            args.worker_id,
            transport=args.transport,
            shm_seg=args.shm_seg,
            prewarm_gate=args.prewarm_gate,
        )
    )


if __name__ == "__main__":
    main()
