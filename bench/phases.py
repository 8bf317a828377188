"""Device time by phase of the train step; host gaps by the trainer's loop spans.

The program names the phases of its jitted step with ``jax.named_scope``
and the parts of its loop with ``jax.profiler.TraceAnnotation``
(``repro.launch.train.PHASES`` and ``LOOP_SPANS``; the copies below are
held equal to them by a test, as ``bench/flops.py`` is).  The TPU trace
names a device op by its HLO instruction and carries no ``op_name``, so
the phase of an op is read from the compiled step's HLO text: instruction
-> ``op_name`` -> phase, the path component right after ``jit(step_fn)/``
with ``vmap(x)`` read as ``x``; anything else is ``unscoped``.  A fusion
carries its root's ``op_name``: XLA fuses Adam, the significance split and
the sent count of one leaf into one pass, named by whichever is its root.
What the compiler made with no ``op_name`` takes the phase of its user.

``reduce`` gives, over the traced window: device seconds per phase, and
the device's idle time by the innermost host span it falls in, the
harness's (``input build``, ``step call``) or the program's (``input``,
``dispatch``, ``sync``, ``log``, ``checkpoint``, ``scale_in``), or
``other``.  A gap that spans several is shared among them, where
``bench/trace.py`` gives each gap the label of its midpoint.

``of_run`` serves the metric readers of a traced run.  The harness hands a
reader the reduced trace, not the trace's directory nor the step's HLO, so
it finds the directory in the harness's ``run`` frame and builds the
cell's step again from the program's own parts to read its HLO (a compile
cache hit after the run).  The rebuilt step is used only if every device
op of the window is one of its instructions, each fusion calling the same
fused computation.  On a program with no scopes every op is ``unscoped``,
and with no loop spans there is no ``sync``: the metrics read nothing.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import sys
from collections import defaultdict

from bench import trace

PHASES = ("fwd_bwd", "clip", "optimizer", "apply", "isp_split", "sent_count",
          "exchange")
UPDATE = PHASES[1:]
LOOP_SPANS = ("train.input", "train.dispatch", "train.sync", "train.log",
              "train.checkpoint", "train.scale_in")
GAP_LABELS = {**trace.GAP_LABELS,
              **{s: s.split(".", 1)[1] for s in LOOP_SPANS}}

_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.-]+) = .*? ([a-z][\w-]*)\((.*)$")
_ROOT = re.compile(r"^\s*ROOT %([\w.-]+) = ")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.-]+) ")
_SCOPED = re.compile(r'op_name="jit\(step_fn\)/(?:vmap\()?(?:%s)\b'
                     % "|".join(PHASES))


def phase_of(op_name) -> str:
    if not op_name:
        return "unscoped"
    head, _, rest = op_name.partition("jit(step_fn)/")
    if head or not rest:
        return "unscoped"
    first = rest.split("/", 1)[0]
    m = re.fullmatch(r"vmap\((.*)\)", first)
    first = m.group(1) if m else first
    return first if first in PHASES else "unscoped"


def hlo_phases(text: str) -> dict:
    """Instruction -> (phase, fused computation or None, the phases of the
    fused computation's instructions), for every instruction of the
    compiled module.  A fusion with no op_name of its own takes its
    root's; an instruction the compiler made with no op_name at all (a
    broadcast of the zeros a gradient accumulates into, an async copy)
    takes the phase of the nearest instruction that uses its result."""
    comp, roots, ins = None, {}, {}
    users, inside = defaultdict(list), defaultdict(set)
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name, _, rest = m.groups()
        on = re.search(r'op_name="([^"]*)"', rest)
        body = re.search(r"calls=%([\w.-]+)", rest)
        ins[name] = (on.group(1) if on else None, body.group(1) if body else None)
        for operand in re.findall(r"%([\w.-]+)", rest.split(")", 1)[0]):
            users[operand].append(name)
        if on:
            inside[comp].add(phase_of(on.group(1)))
        if _ROOT.match(line):
            roots[comp] = name
    own = {}
    for name, (on, body) in ins.items():
        while on is None and body in roots:
            on, body = ins[roots[body]]
        own[name] = on

    def by_use(name):
        seen, front = {name}, [name]
        while front:
            named = [own[n] for n in front if own.get(n)]
            if named:
                return phase_of(named[0])
            front = [u for n in front for u in users[n] if u not in seen]
            seen.update(front)
        return "unscoped"

    return {name: (phase_of(own[name]) if own[name] else by_use(name), body,
                   sorted(inside.get(body, ())))
            for name, (_, body) in ins.items()}


def extract(log_dir: str) -> dict:
    """``bench/trace.extract``'s record, with the program's loop spans."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    keep = set(trace.HOST_SPANS) | set(LOOP_SPANS)
    ops, spans, calls = [], [], {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            if plane.name.startswith("/device:TPU:") and line.name == "XLA Ops":
                for e in line.events:
                    name = trace.op_name(e.name)
                    ops.append([name, int(e.start_ns), int(e.duration_ns),
                                plane.name])
                    body = re.search(r"calls=%([\w.-]+)", e.name)
                    calls[name] = body.group(1) if body else None
            elif plane.name.startswith("/host:"):
                spans += [[e.name, int(e.start_ns), int(e.duration_ns)]
                          for e in line.events if e.name in keep]
    return {"device_ops": ops, "host_spans": spans, "calls": calls}


def reduce(record: dict) -> dict:
    """Window totals in seconds, averaged over the traced devices.  The
    record's ``phases`` maps an instruction to its phase; an op it does not
    name is ``unscoped``."""
    t0, t1 = trace.window_of(record)
    phases = record.get("phases", {})
    devices = sorted({op[3] for op in record["device_ops"]})
    n_dev = max(len(devices), 1)
    by_phase = defaultdict(float)
    busy, gaps = 0.0, []
    for dev in devices:
        intervals = []
        for name, start, dur, plane in record["device_ops"]:
            if plane != dev or name.split(".")[0] in trace.CONTAINERS:
                continue
            s, e = max(start, t0), min(start + dur, t1)
            if e > s:
                intervals.append((s, e))
                by_phase[phases.get(name, "unscoped")] += (e - s) / 1e9 / n_dev
        merged = trace._union(intervals)
        busy += sum(e - s for s, e in merged) / 1e9 / n_dev
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    labels = _labelled(record["host_spans"])
    starts = [lo for lo, _, _ in labels]
    by_label = defaultdict(float)
    for s, e in gaps:
        # a gap is shared among the spans it overlaps
        rest = e - s
        k = max(bisect.bisect_right(starts, s) - 1, 0)
        while k < len(labels) and labels[k][0] < e:
            lo, hi, label = labels[k]
            part = min(e, hi) - max(s, lo)
            if part > 0:
                by_label[label] += part / 1e9 / n_dev
                rest -= part
            k += 1
        if rest > 0:
            by_label["other"] += rest / 1e9 / n_dev
    host = defaultdict(float)
    for n, s, d in record["host_spans"]:
        if n in LOOP_SPANS:
            host[n] += max(0, min(s + d, t1) - max(s, t0)) / 1e9
    return {
        "window_s": (t1 - t0) / 1e9,
        "busy_s": busy,
        "device_phases": dict(sorted(by_phase.items(), key=lambda kv: -kv[1])),
        "idle_gaps": sorted(([k, v] for k, v in by_label.items()),
                            key=lambda kv: -kv[1]),
        "loop_spans": dict(host),
    }


def _labelled(host_spans) -> list:
    """The host spans cut into pieces that no span boundary crosses, each
    (start, end, label) labelled by the innermost span that covers it: the
    one that started last."""
    spans = sorted((s, s + d, n) for n, s, d in host_spans if n in GAP_LABELS)
    edges = sorted({x for s, e, _ in spans for x in (s, e)})
    out, active, j = [], [], 0
    for lo, hi in zip(edges, edges[1:]):
        while j < len(spans) and spans[j][0] <= lo:
            active.append(spans[j])
            j += 1
        active = [sp for sp in active if sp[1] > lo]
        if active:
            out.append((lo, hi, GAP_LABELS[max(active)[2]]))
    return out


# ---- the traced run the readers are called from ---------------------------------


def _trace_dir():
    """The harness's trace directory, from its ``run`` frame."""
    from bench import harness

    f = sys._getframe(1)
    while f is not None and f.f_code is not harness.run.__code__:
        f = f.f_back
    return f.f_locals.get("trace_dir") if f is not None else None


def step_hlo(cell) -> str:
    """The compiled HLO of the cell's train step, built as the trainer
    builds it (``repro.launch.train.train``) for the same shapes."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from bench import harness
    from repro import optim
    from repro.core.isp import ISPConfig
    from repro.dist.compression import CompressionConfig
    from repro.launch import train as tr
    from repro.models.transformer import LM

    arch = harness.program_arch(cell.cfg)
    tr._EXTRA[arch.name] = arch  # as the harness registers it
    args = tr.parse_args(harness.train_argv(cell, 0, 1))
    lm = LM(arch)
    opt = optim.make(args.optimizer, args.lr)
    mode = tr.MODES[args.mode]
    isp = ISPConfig(v=args.isp_v) if args.mode.startswith("isp") else None
    comp = (CompressionConfig(scheme=args.scheme, budget=args.budget)
            if mode.pod else None)
    pool = args.workers

    def fresh(key):
        params = lm.init(key)
        o, r = opt.init(params), jax.tree.map(jnp.zeros_like, params)
        if mode.pod:
            o, r = tr.lift_pod(o, pool), tr.lift_pod(r, pool)
        return params, o, r

    state = jax.eval_shape(fresh, jax.random.PRNGKey(0))
    batch = {k: jax.ShapeDtypeStruct((pool * args.per_worker_batch, args.seq),
                                     jnp.int32) for k in ("tokens", "labels")}
    on_dev = SingleDeviceSharding(jax.devices()[0])
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=on_dev,
                                       weak_type=a.weak_type),
        (*state, batch))

    def compiled():
        step = mode.build_step(lm, opt, isp, comp, pool)
        return step.lower(*shapes).compile().as_text()

    text = compiled()
    if not _SCOPED.search(text):
        # the persistent cache's key leaves out the op_names (JAX strips
        # debug info before hashing it), so what it served may be a build of
        # the same step from before the scopes: compile afresh
        from jax.experimental.compilation_cache import compilation_cache as cc

        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            text = compiled()
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            cc.reset_cache()
    return text


def of_run(ctx):
    """The reduced record of the traced run whose readers call this, or
    None where there is no trace to read; read once, kept on ``ctx``."""
    if not hasattr(ctx, "phases"):
        log_dir = _trace_dir()
        ctx.phases = _read(ctx, log_dir) if log_dir else None
    return ctx.phases


def _read(ctx, log_dir: str) -> dict:
    from bench import harness

    record = extract(log_dir)
    t0, t1 = trace.window_of(record)
    window = {op[0] for op in record["device_ops"]
              if op[1] < t1 and op[1] + op[2] > t0
              and op[0].split(".")[0] not in trace.CONTAINERS}
    hlo = hlo_phases(step_hlo(ctx.cell)) if window else {}
    # the rebuilt step is the one that ran if it holds every op of the
    # window, each fusion calling the same fused computation
    strange = sorted(n for n in window
                     if n not in hlo or hlo[n][1] != record["calls"][n])
    if strange:
        harness.info(f"phases: {len(strange)} device ops of the window differ "
                     f"from the rebuilt step, e.g. {strange[:3]}; not read")
        hlo = {}
    record["phases"] = {n: hlo[n][0] for n in window if n in hlo}
    out = reduce(record)
    steps = max(ctx.steps, 1)
    phases = out["device_phases"]
    harness.info("device_phases (s in the window): " + ", ".join(
        f"{k} {v:.4f}" for k, v in phases.items())
        + f"; busy {out['busy_s']:.4f}; per step (ms): " + ", ".join(
        f"{k} {1e3 * v / steps:.3f}" for k, v in phases.items()))
    harness.info("idle gaps by span (s): " + ", ".join(
        f"{k} {v:.4f}" for k, v in out["idle_gaps"]))
    harness.info("top device ops: " + "; ".join(
        f"{n} {s:.3f} s {hlo[n][0]} (fused: {' '.join(hlo[n][2])})"
        if n in hlo else f"{n} {s:.3f} s" for n, s in ctx.trace["device_ops"]))
    return out
