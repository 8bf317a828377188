"""End-to-end training driver over pluggable execution substrates.

Two registries keep ``main()`` flat as substrates accumulate (DESIGN.md §9):

* ``RUNTIMES`` — *where* the job runs: ``inproc`` (this process: the jitted
  single-host loop below) or ``faas`` (real multi-process serverless
  runtime, ``repro.runtime``).
* ``MODES`` — the in-process consistency/exchange mode: ``bsp``, ``isp``
  (error-feedback filter on the update), ``isp-pod`` (per-pod divergent
  state + compressed collective exchange). Each mode bundles its step
  builder and its scale-in transition, so the training loop calls one
  registry hook instead of branching.

The in-process runtime realizes the MLLess loop as a pod would (DESIGN.md
§2): data-parallel training with the ISP significance filter on the
gradient exchange and the scale-in auto-tuner driving *elastic weak
scaling* — evicting a worker shrinks the global batch (B_g = P*B, paper
§3.2) and the step is re-lowered for the smaller pool, exactly the
checkpoint -> re-mesh -> restore transition a pod would perform.

Fault tolerance: deterministic step-indexed checkpoints (atomic rename);
``--restore`` resumes from the newest one, reproducing the optimizer/filter
state bit-exactly. Eviction writes a checkpoint first (the transition IS a
restore), so a node failure at any point costs at most one interval.

Usage (CPU example sizes):
  python -m repro.launch.train --arch lm-100m --steps 300 --workers 4 \
      --per-worker-batch 4 --seq 512 --mode isp --autotune \
      --checkpoint-dir /tmp/ckpt
  python -m repro.launch.train --arch xlstm-1.3b --smoke --steps 20
  python -m repro.launch.train --runtime faas --workload pmf --steps 60 \
      --workers 4 --autotune --run-dir /tmp/faas
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import optim
from repro.checkpoint import store as ckpt
from repro.configs import ARCH_NAMES, get_arch, get_smoke
from repro.core.autotuner import AutoTunerConfig, ScaleInAutoTuner
from repro.core.billing import CommModel, faas_cost
from repro.core.isp import ISPConfig, communicated_fraction
from repro.data.tokens import TokenPipeline
from repro.dist import elastic as dist_elastic
from repro.dist.compression import (
    CompressionConfig,
    apply_combined,
    isp_compressed_step,
)
from repro.launch.compile_cache import use_compile_cache
from repro.models.config import ArchConfig, BlockSpec, FF, Mixer, uniform_groups
from repro.models.transformer import LM
from repro.optim import apply_updates, clip_by_global_norm

PyTree = Any

# the deliverable's "~100M model": 12L x d768 SwiGLU, 32k vocab -> ~103M
LM_100M = ArchConfig(
    name="lm-100m",
    family="dense",
    d_model=768,
    n_heads=12,
    n_kv_heads=12,
    d_ff=2048,
    vocab_size=32_768,
    groups=uniform_groups(BlockSpec(Mixer.GLOBAL_ATTN, FF.SWIGLU), 12),
    max_seq_len=8192,
    sub_quadratic=False,
)

LM_8M = dataclasses.replace(
    LM_100M, name="lm-8m", d_model=256, n_heads=8, n_kv_heads=8, d_ff=512,
    vocab_size=8192,
    groups=uniform_groups(BlockSpec(Mixer.GLOBAL_ATTN, FF.SWIGLU), 4),
)

_EXTRA = {"lm-100m": LM_100M, "lm-8m": LM_8M}


def resolve_arch(name: str, smoke: bool) -> ArchConfig:
    if name in _EXTRA:
        return _EXTRA[name]
    return get_smoke(name) if smoke else get_arch(name)


@dataclasses.dataclass
class TrainState:
    params: PyTree
    opt_state: Any
    residual: PyTree  # ISP error-feedback residual
    step: int
    pool: int  # current worker count (elastic weak scaling)


# The phases of a train step, each a ``jax.named_scope`` in ``make_step`` /
# ``make_pod_step``.  A scope is trace-time metadata: it reaches every HLO
# instruction of its phase as ``op_name="jit(step_fn)/<phase>/..."`` (under
# the pod vmap, ``jit(step_fn)/vmap(<phase>)/...``), so a profile can split
# device time by phase, and it leaves the computation as it is.  JAX's
# persistent compile cache hashes the module without this debug info, so an
# entry compiled before a scope changed comes back with the old metadata.
PHASES = ("fwd_bwd", "clip", "optimizer", "apply", "isp_split", "sent_count",
          "exchange")

# The parts of one iteration of ``train()``'s loop, each a
# ``jax.profiler.TraceAnnotation`` on the profiler's clock.  With no profiler
# running an annotation costs a branch.
LOOP_SPANS = ("train.input", "train.dispatch", "train.sync", "train.log",
              "train.checkpoint", "train.scale_in")


def make_step(lm: LM, optimizer, isp: Optional[ISPConfig], clip: float = 1.0):
    """One jitted train step for a fixed pool size.

    BSP: plain update. ISP: optimizer update -> residual accumulate ->
    significance split -> apply only the significant part (the residual
    stays local; on a pod the significant part is what crosses the pod
    axis — see dist.compression for the collective form).
    """

    def step_fn(params, opt_state, residual, batch):
        with jax.named_scope("fwd_bwd"):
            (loss, metrics), grads = jax.value_and_grad(
                lm.train_loss, has_aux=True
            )(params, batch)
        if clip:
            with jax.named_scope("clip"):
                grads = clip_by_global_norm(grads, clip)
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
        if isp is None:
            with jax.named_scope("apply"):
                params = apply_updates(params, updates)
            sent_frac = jnp.asarray(1.0, jnp.float32)
        else:
            from repro.core.isp import significance_split

            with jax.named_scope("isp_split"):
                v_t = isp.threshold(opt_state.step)

                def split(u, x, r):
                    return significance_split(r + u, x, v_t,
                                              isp.absolute_floor)

                out = jax.tree.map(split, updates, params, residual)
                td = jax.tree.structure(params)
                ls = td.flatten_up_to(out)
                sig = td.unflatten([l[0] for l in ls])
                residual = td.unflatten([l[1] for l in ls])
                masks = td.unflatten([l[2] for l in ls])
                params = apply_updates(params, sig)
            with jax.named_scope("sent_count"):
                sent_frac = communicated_fraction(masks)
        return params, opt_state, residual, loss, sent_frac

    return jax.jit(step_fn, donate_argnums=(0, 1, 2))


def lift_pod(tree: PyTree, n_pods: int) -> PyTree:
    """Stack a shared tree into per-pod state: every leaf gains a leading
    (n_pods,) dim. Used for the divergent optimizer moments and residuals
    of the pod path (the paper's per-worker state)."""
    return jax.tree.map(lambda x: jnp.repeat(x[None], n_pods, axis=0), tree)


def make_pod_step(
    lm: LM,
    optimizer,
    isp: ISPConfig,
    comp: CompressionConfig,
    n_pods: int,
    clip: float = 1.0,
):
    """One jitted ISP-pod train step (DESIGN.md §2) for a fixed pool size.

    The global batch arrives as (P*B, ...) and is reshaped so dim 0 is the
    pod axis; each pod runs its own optimizer on its own shard (divergent
    moments), then the error-feedback compressed exchange
    (``dist.compression.isp_compressed_step``) combines the significant
    parts into the shared parameters. It is the one pod step: the trainer
    runs it under ``vmap`` on one device, and ``launch.dryrun`` lowers it
    with the leading dim sharded over 'pod', where the sum over pods is a
    dense all-reduce.
    """

    def step_fn(params, opt_pod, res_pod, batch):
        batch_p = jax.tree.map(
            lambda x: x.reshape((n_pods, x.shape[0] // n_pods) + x.shape[1:]),
            batch,
        )

        def pod_fn(opt_state, b):
            with jax.named_scope("fwd_bwd"):
                (loss, metrics), grads = jax.value_and_grad(
                    lm.train_loss, has_aux=True
                )(params, b)
            if clip:
                with jax.named_scope("clip"):
                    grads = clip_by_global_norm(grads, clip)
            with jax.named_scope("optimizer"):
                updates, opt_state = optimizer.update(grads, opt_state, params)
            return updates, opt_state, loss

        updates, opt_pod, losses = jax.vmap(pod_fn)(opt_pod, batch_p)
        with jax.named_scope("exchange"):
            v_t = isp.threshold(opt_pod.step[0])
            combined, res_pod, stats = isp_compressed_step(
                comp, updates, params, res_pod, v_t,
                floor=isp.absolute_floor,
            )
            params = apply_combined(params, combined)
        return (params, opt_pod, res_pod, jnp.mean(losses),
                stats["sent_fraction"])

    return jax.jit(step_fn, donate_argnums=(0, 1, 2))


# -- mode registry (DESIGN.md §9.5) -------------------------------------------
#
# A mode owns (a) how a train step is built for a pool size and (b) what a
# scale-in transition does to the train state. New exchange modes register
# here instead of adding branches to the training loop.


@dataclasses.dataclass(frozen=True)
class TrainMode:
    """One in-process exchange mode."""

    name: str
    pod: bool  # per-pod (lifted) optimizer/residual state
    build_step: Any  # (lm, optimizer, isp, comp, pool) -> jitted step_fn
    scale_in: Any  # (args, st, plan, isp) -> TrainState (pool shrunk by 1)


MODES: dict[str, TrainMode] = {}


def register_mode(mode: TrainMode) -> TrainMode:
    MODES[mode.name] = mode
    return mode


def _scale_in_flat(args, st: TrainState, plan, isp) -> TrainState:
    """bsp/isp scale-in: flush the ISP residual into the params (the paper's
    leaving-worker model averaging, error-feedback form — no update mass is
    lost), checkpoint, shrink the pool."""
    if isp is not None:
        st.params = apply_updates(st.params, st.residual)
        st.residual = jax.tree.map(jnp.zeros_like, st.residual)
    if args.checkpoint_dir:
        save_checkpoint(args.checkpoint_dir, st)
    st.pool -= 1
    return st


def _scale_in_pod(args, st: TrainState, plan, isp) -> TrainState:
    """isp-pod scale-in: dist.elastic owns the transition — the evicted
    pod's residual is flushed into the shared params and its optimizer/
    residual slices dropped; the transition IS a checkpoint restore under
    the smaller pool's mesh whenever this host can build it."""
    tr = dist_elastic.plan_transition(plan, st.pool, st.pool - 1)
    st.params, st.opt_state, st.residual = dist_elastic.apply_transition(
        tr, st.params, st.opt_state, st.residual
    )
    st.pool = tr.new_pods
    if args.checkpoint_dir:
        save_checkpoint(args.checkpoint_dir, st)
        if jax.device_count() >= int(np.prod(tr.new_mesh_shape)):
            tree = {"params": st.params, "opt": st.opt_state,
                    "residual": st.residual}
            out = dist_elastic.resharded_restore(
                args.checkpoint_dir, st.step, tree, tr.new_pods
            )
            st.params = out["params"]
            st.opt_state = out["opt"]
            st.residual = out["residual"]
    return st


register_mode(TrainMode(
    name="bsp", pod=False,
    build_step=lambda lm, opt, isp, comp, pool: make_step(lm, opt, None),
    scale_in=_scale_in_flat,
))
register_mode(TrainMode(
    name="isp", pod=False,
    build_step=lambda lm, opt, isp, comp, pool: make_step(lm, opt, isp),
    scale_in=_scale_in_flat,
))
register_mode(TrainMode(
    name="isp-pod", pod=True,
    build_step=lambda lm, opt, isp, comp, pool: make_pod_step(
        lm, opt, isp, comp, pool
    ),
    scale_in=_scale_in_pod,
))


def save_checkpoint(d: str, st: TrainState) -> str:
    return ckpt.save(
        d, st.step,
        {"params": st.params, "opt": st.opt_state, "residual": st.residual},
        extra={"pool": st.pool},
    )


def restore_checkpoint(d: str, st: TrainState) -> TrainState:
    step = ckpt.latest_step(d)
    if step is None:
        return st
    tree = ckpt.restore(
        d, step,
        {"params": st.params, "opt": st.opt_state, "residual": st.residual},
    )
    extra = ckpt.manifest_extra(d, step)
    return TrainState(
        params=tree["params"], opt_state=tree["opt"],
        residual=tree["residual"], step=step, pool=extra.get("pool", st.pool),
    )


def train(args) -> dict:
    cfg = resolve_arch(args.arch, args.smoke)
    lm = LM(cfg)
    key = jax.random.PRNGKey(args.seed)
    optimizer = optim.make(args.optimizer, args.lr)
    mode = MODES[args.mode]
    pod_mode = mode.pod
    isp = ISPConfig(v=args.isp_v) if args.mode.startswith("isp") else None
    # --wire-scheme overrides the byte-accounting codec (else it derives
    # from the exchange scheme); repro.wire either way. 'auto' is per-leaf
    # data-dependent — not resolvable inside jit — so the traced pod
    # accounting keeps the derived codec.
    wire_override = getattr(args, "wire_scheme", None)
    if wire_override == "auto":
        wire_override = None
    comp = (
        CompressionConfig(
            scheme=getattr(args, "scheme", "dense"),
            budget=getattr(args, "budget", 0.01),
            wire=wire_override,
        )
        if pod_mode
        else None
    )

    params = lm.init(key)
    n_params = lm.n_params()
    print(f"arch={cfg.name} params={n_params:,} mode={args.mode} "
          f"workers={args.workers}")

    def fresh_state(pool: int) -> TrainState:
        opt0 = optimizer.init(params)
        res0 = jax.tree.map(jnp.zeros_like, params)
        if pod_mode:  # per-pod divergent optimizer moments + residuals
            opt0, res0 = lift_pod(opt0, pool), lift_pod(res0, pool)
        return TrainState(params=params, opt_state=opt0, residual=res0,
                          step=0, pool=pool)

    st = fresh_state(args.workers)
    if args.restore and args.checkpoint_dir:
        step = ckpt.latest_step(args.checkpoint_dir)
        if step is not None and pod_mode:
            # per-pod state shapes depend on the checkpointed pool size —
            # rebuild the restore template at that pool first
            pool = ckpt.manifest_extra(args.checkpoint_dir, step).get(
                "pool", st.pool
            )
            st = fresh_state(pool)
        st = restore_checkpoint(args.checkpoint_dir, st)
        print(f"restored step={st.step} pool={st.pool}")

    # the weak-scaling contract B_g = P * B lives in the elastic plan
    plan = dist_elastic.ElasticPlan(
        initial_pods=max(args.workers, st.pool),
        per_pod_batch=args.per_worker_batch,
    )

    tuner = None
    if args.autotune:
        tuner = ScaleInAutoTuner(
            AutoTunerConfig(
                sched_interval_s=args.sched_interval,
                delta_s=args.sched_interval / 2,
                min_workers=1,
            ),
            st.pool,
        )

    def build_step(pool: int):
        return mode.build_step(lm, optimizer, isp, comp, pool)

    step_fn = build_step(st.pool)
    history = []
    worker_seconds = 0.0
    t_job0 = time.perf_counter()
    span = jax.profiler.TraceAnnotation

    while st.step < args.steps:
        with span("train.input"):
            # weak scaling (paper §3.2): global batch = pool * per-worker batch
            gb = plan.global_batch(st.pool)
            pipe = TokenPipeline(cfg.vocab_size, args.seq, gb, seed=args.seed)
            batch = pipe.next_batch(st.step)
        t0 = time.perf_counter()
        with span("train.dispatch"):
            st.params, st.opt_state, st.residual, loss, sent = step_fn(
                st.params, st.opt_state, st.residual, batch
            )
        with span("train.sync"):
            loss, sent = float(loss), float(sent)
        dt = time.perf_counter() - t0
        worker_seconds += dt * st.pool
        st.step += 1
        with span("train.log"):
            history.append(
                {"step": st.step, "loss": loss, "sent_fraction": sent,
                 "pool": st.pool, "step_s": dt}
            )
            if st.step % args.log_every == 0:
                print(f"step {st.step:5d} pool={st.pool:2d} loss={loss:.4f} "
                      f"sent={sent:.3f} {dt*1e3:.0f}ms")
            scale_in = False
            if tuner is not None:
                tuner.observe(st.step, loss, dt)
                scale_in = tuner.decide().remove_worker and st.pool > 1

        if args.checkpoint_dir and st.step % args.checkpoint_every == 0:
            with span("train.checkpoint"):
                save_checkpoint(args.checkpoint_dir, st)

        if scale_in:
            with span("train.scale_in"):
                # elastic scale-in: reintegrate -> checkpoint -> re-lower,
                # with the mode registry owning the transition semantics
                st = mode.scale_in(args, st, plan, isp)
                step_fn = build_step(st.pool)  # re-lower
            print(f"  [autotuner] scale-in -> pool={st.pool} "
                  f"(global batch {plan.global_batch(st.pool)})")

    wall = time.perf_counter() - t_job0
    # bill the modelled topology the job declares (paper: one Redis VM per
    # update-store shard), not a hardcoded single shard
    bill = faas_cost(
        [worker_seconds], wall, n_redis=getattr(args, "n_brokers", 1)
    )
    result = {
        "arch": cfg.name,
        "n_params": n_params,
        "final_loss": history[-1]["loss"] if history else None,
        "steps": st.step,
        "final_pool": st.pool,
        "wall_s": wall,
        "worker_seconds": worker_seconds,
        "mean_sent_fraction": float(
            np.mean([h["sent_fraction"] for h in history])
        ) if history else None,
        "faas_cost_usd": bill.total,
        "history": history,
    }
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
    result["params"] = st.params  # final parameters, for in-process callers
    return result


# -- runtime registry ---------------------------------------------------------
#
# A runtime is a whole execution substrate: it receives the parsed args and
# returns the result dict. ``inproc`` is the jitted loop above; ``faas`` is
# the real multi-process serverless runtime.

RUNTIMES: dict[str, Any] = {}


def register_runtime(name: str):
    def deco(fn):
        RUNTIMES[name] = fn
        return fn

    return deco


register_runtime("inproc")(train)


def _parse_retunes(specs) -> tuple:
    """--retune STEP:JSON (repeatable) -> scripted_retunes tuples."""
    out = []
    for s in specs or ():
        step, sep, body = s.partition(":")
        if not sep:
            raise SystemExit(f"--retune {s!r}: expected STEP:JSON")
        try:
            out.append((int(step), json.loads(body)))
        except (ValueError, json.JSONDecodeError) as e:
            raise SystemExit(f"--retune {s!r}: expected STEP:JSON ({e})")
    return tuple(out)


def _topology_args(args) -> dict:
    """Resolve the topology-tuning CLI flags into FaaSJobConfig fields.

    Live re-sharding moves little data only when leaves are chunked, so
    when tuning is on and --shard-split-bytes was not given we default to
    the consistent-hash ring over 64 KiB chunks; the plain path keeps the
    greedy whole-leaf partitioner (bit-identical to prior releases).
    """
    retunes = _parse_retunes(getattr(args, "retune", None))
    topo = bool(getattr(args, "topology_tune", False))
    split = int(getattr(args, "shard_split_bytes", 0) or 0)
    partitioner = "greedy"
    if (topo or retunes) and split == 0:
        split = 65536
        partitioner = "ring"
    return {
        "topology_tune": topo,
        "scripted_retunes": retunes,
        "partitioner": partitioner,
        "shard_split_bytes": split,
    }


def _fleet_faas(args, run_dir: str) -> dict:
    """--jobs: N concurrent jobs on ONE shared pool (runtime.scheduler).

    The jobs file maps job id -> FaaSJobConfig field overrides (optionally
    under a top-level "jobs" key, with "pool_budget" alongside)::

        {"pool_budget": 4,
         "jobs": {"pmf0": {"workload": "pmf", "n_workers": 3,
                           "total_steps": 60},
                  "lr0": {"workload": "lr", "n_workers": 2,
                          "total_steps": 40, "consistency": "ssp"}}}

    CLI flags (--n-brokers, --transport, --wire-quant, ...) are the fleet
    defaults; per-job overrides win.  Pool topology must agree across jobs
    (they share the broker processes).
    """
    from repro.runtime import FaaSJobConfig, FleetConfig, run_fleet

    with open(args.jobs) as f:
        doc = json.load(f)
    specs = doc.get("jobs", doc) if isinstance(doc, dict) else None
    if not isinstance(specs, dict) or not specs:
        raise SystemExit(f"--jobs {args.jobs}: expected a job-id mapping")
    pool_budget = args.pool_budget
    if pool_budget is None and isinstance(doc, dict):
        pool_budget = doc.get("pool_budget")
    topo = _topology_args(args)
    if topo["scripted_retunes"]:
        raise SystemExit(
            "--retune is not supported with --jobs: the fleet's broker "
            "pool is shared, so no job may re-shard it live"
        )
    fields = {f.name for f in dataclasses.fields(FaaSJobConfig)}
    jobs = {}
    for jid, spec in specs.items():
        unknown = set(spec) - fields
        if unknown:
            raise SystemExit(
                f"--jobs job {jid!r}: unknown fields {sorted(unknown)}"
            )
        base = dict(
            run_dir=os.path.join(run_dir, "jobs", str(jid)),
            workload=args.workload,
            n_workers=args.workers,
            total_steps=args.steps,
            invocation_steps=args.invocation_steps,
            checkpoint_every=args.checkpoint_every,
            optimizer=args.optimizer,
            lr=args.lr,
            isp_v=args.isp_v,
            wire_scheme=args.wire_scheme or "auto",
            wire_quant=args.wire_quant,
            wire_impl=getattr(args, "wire_impl", "numpy"),
            hostperf=getattr(args, "hostperf", False),
            n_brokers=getattr(args, "n_brokers", 1),
            transport=getattr(args, "transport", "tcp"),
            consistency=getattr(args, "consistency", "isp"),
            slack=getattr(args, "slack", 3),
            autotune=args.autotune,
            # observe-only under the fleet: keep the exact layout the user
            # asked for (no ring/split default — the pool never re-shards)
            topology_tune=topo["topology_tune"],
            shard_split_bytes=int(getattr(args, "shard_split_bytes", 0) or 0),
            seed=args.seed,
        )
        base.update(spec)
        jobs[str(jid)] = FaaSJobConfig(**base)
    result = run_fleet(FleetConfig(
        run_dir=run_dir, jobs=jobs, pool_budget=pool_budget,
    ))
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1, default=str)
    return result


@register_runtime("faas")
def train_faas(args) -> dict:
    """Run the job on the multi-process FaaS runtime (repro.runtime)."""
    import tempfile

    from repro.core.autotuner import AutoTunerConfig
    from repro.runtime import FaaSJobConfig, run_job

    run_dir = args.run_dir or args.checkpoint_dir or tempfile.mkdtemp(
        prefix="repro_faas_"
    )
    if getattr(args, "jobs", None):
        if getattr(args, "chaos", None):
            raise SystemExit(
                "--chaos is not supported with --jobs: a fault plan "
                "SIGKILLs pool processes shared by every tenant"
            )
        return _fleet_faas(args, run_dir)
    chaos_plan = None
    if getattr(args, "chaos", None):
        from repro.runtime.faults import parse_chaos_arg

        chaos_plan = parse_chaos_arg(
            args.chaos, n_workers=args.workers,
            n_shards=getattr(args, "n_brokers", 1), total_steps=args.steps,
        )
    topo = _topology_args(args)
    cfg = FaaSJobConfig(
        run_dir=run_dir,
        workload=args.workload,
        workload_cfg=json.loads(args.workload_cfg) if args.workload_cfg
        else {},
        n_workers=args.workers,
        total_steps=args.steps,
        invocation_steps=args.invocation_steps,
        checkpoint_every=args.checkpoint_every,
        optimizer=args.optimizer,
        lr=args.lr,
        isp_v=args.isp_v,
        wire_scheme=args.wire_scheme or "auto",
        wire_quant=args.wire_quant,
        wire_impl=getattr(args, "wire_impl", "numpy"),
        hostperf=getattr(args, "hostperf", False),
        n_brokers=getattr(args, "n_brokers", 1),
        transport=getattr(args, "transport", "tcp"),
        consistency=getattr(args, "consistency", "isp"),
        slack=getattr(args, "slack", 3),
        autotune=args.autotune,
        tuner=AutoTunerConfig(
            sched_interval_s=args.sched_interval,
            delta_s=args.sched_interval / 2,
        ),
        topology_tune=topo["topology_tune"],
        scripted_retunes=topo["scripted_retunes"],
        partitioner=topo["partitioner"],
        shard_split_bytes=topo["shard_split_bytes"],
        seed=args.seed,
        chaos=None if chaos_plan is None else chaos_plan.to_spec(),
    )
    if chaos_plan is not None and any(
        e.kind == "supervisor_kill" for e in chaos_plan.events
    ):
        # the supervisor will kill itself mid-job: drive it from outside
        # so it can be re-executed against its journal
        from repro.runtime.faults import run_job_resilient

        result = run_job_resilient(cfg)
    else:
        result = run_job(cfg)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1, default=str)
    return result


def parse_args(argv: Optional[list[str]] = None) -> argparse.Namespace:
    """The driver's command line (``argv`` None reads ``sys.argv``)."""
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--runtime", default="inproc",
                    choices=tuple(sorted(RUNTIMES)),
                    help="execution substrate (see module docstring)")
    ap.add_argument("--arch", default="lm-8m",
                    choices=tuple(_EXTRA) + ARCH_NAMES)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--per-worker-batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--mode", choices=tuple(sorted(MODES)), default="bsp")
    ap.add_argument("--isp-v", type=float, default=0.7)
    ap.add_argument("--scheme", choices=("dense", "topk", "bitmap"),
                    default="dense",
                    help="isp-pod exchange scheme (dist.compression)")
    ap.add_argument("--budget", type=float, default=0.01,
                    help="topk fraction kept per block")
    ap.add_argument("--wire-scheme", default=None,
                    choices=("auto", "dense", "sparse", "bitmap"),
                    help="repro.wire update codec, both runtimes: the faas "
                    "workers' encoder AND the isp-pod byte accounting "
                    "(default: auto for faas, derived from --scheme inproc)")
    ap.add_argument("--wire-quant", default="none",
                    choices=("none", "fp16", "bf16"),
                    help="faas: value quantization with error-feedback "
                    "residual (repro.wire)")
    ap.add_argument("--wire-impl", default="numpy",
                    choices=("numpy", "pallas", "auto"),
                    help="faas: codec backend — numpy reference, fused "
                    "Pallas kernels (bit-identical bytes), or per-leaf "
                    "auto selection (DESIGN.md §15)")
    ap.add_argument("--hostperf", action="store_true",
                    help="faas: spawn workers under the tuned host env "
                    "(launch/hostperf.py)")
    ap.add_argument("--optimizer", default="adam",
                    choices=("adam", "sgd", "nesterov"))
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--autotune", action="store_true")
    ap.add_argument("--sched-interval", type=float, default=20.0)
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--restore", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    # faas-runtime options
    ap.add_argument("--workload", default="pmf",
                    help="faas runtime workload (repro.runtime.workload)")
    ap.add_argument("--workload-cfg", default=None,
                    help="JSON overrides for the workload config")
    ap.add_argument("--invocation-steps", type=int, default=1_000_000,
                    help="faas: steps per function invocation")
    ap.add_argument("--n-brokers", type=int, default=1,
                    help="update-store shards (runtime.sharding): faas "
                    "spawns one broker process per shard; both runtimes "
                    "bill n_redis == n_brokers")
    ap.add_argument("--transport", default="tcp", choices=("tcp", "shm"),
                    help="faas: worker<->shard update-path channel "
                    "(repro.wire): persistent loopback TCP or zero-copy "
                    "shared-memory rings (same accounted bytes)")
    ap.add_argument("--consistency", default="isp", choices=("isp", "ssp"),
                    help="faas: pull-barrier model — 'isp' full per-step "
                    "barrier (default), 'ssp' bounded staleness (a pull at "
                    "step t waits only for steps <= t - slack - 1)")
    ap.add_argument("--slack", type=int, default=3,
                    help="faas: SSP staleness bound (ignored under isp)")
    ap.add_argument("--topology-tune", action="store_true",
                    help="faas: co-tune {n_brokers, transport, wire_scheme,"
                    " shard_split_bytes} online — explore-then-commit over "
                    "neighbouring cells with WAL-coordinated live "
                    "re-sharding at invocation boundaries (DESIGN.md §16); "
                    "requires --consistency isp, no --jobs re-shard")
    ap.add_argument("--chaos", default=None, metavar="SEED:SPEC",
                    help="faas: seeded fault-injection plan "
                         "(runtime/faults.py) — SEED:auto expands the "
                         "default randomized multi-fault schedule, "
                         "SEED:[{\"kind\":...,\"step\":...}] is explicit; "
                         "incompatible with --jobs")
    ap.add_argument("--retune", action="append", metavar="STEP:JSON",
                    help="faas: force one live re-shard when the frontier "
                    "reaches STEP, e.g. '4:{\"n_brokers\":2}' (repeatable; "
                    "disables the online tuner — scripted topologies only)")
    ap.add_argument("--shard-split-bytes", type=int, default=0,
                    help="faas: split update-store leaves into chunks of at "
                    "most this many bytes before sharding (0 = whole "
                    "leaves; tuning defaults this to 65536 with the "
                    "consistent-hash ring partitioner)")
    ap.add_argument("--run-dir", default=None,
                    help="faas: checkpoints + worker logs directory")
    ap.add_argument("--jobs", default=None,
                    help="faas: JSON file of N jobs to run CONCURRENTLY on "
                    "one shared broker/worker pool (runtime.scheduler); "
                    "maps job id -> FaaSJobConfig overrides")
    ap.add_argument("--pool-budget", type=int, default=None,
                    help="faas --jobs: max concurrent active (worker, job) "
                    "pairs; the scheduler evicts fair-share beyond it")
    return ap.parse_args(argv)


def main() -> None:
    use_compile_cache()
    args = parse_args()
    res = RUNTIMES[args.runtime](args)
    slim = {k: v for k, v in res.items()
            if k not in ("history", "updates", "params")}
    if isinstance(slim.get("jobs"), dict):  # fleet: per-job histories too
        slim["jobs"] = {
            j: {k: v for k, v in r.items() if k != "history"}
            for j, r in slim["jobs"].items()
        }
    print(json.dumps(slim, indent=1, default=str))


if __name__ == "__main__":
    main()
