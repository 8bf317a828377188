"""Device time under the program's named scopes inside the model, and the
held experts' matmul FLOPs.

The program names the parts of its expert layer and its attention core
with ``jax.named_scope`` (``repro.models.moe.SCOPES`` and
``repro.models.attention.SCOPES``; the copy below is held equal to them by
a test).  These scopes sit inside the step's ``fwd_bwd`` phase, under the
layer scan, rematerialization and autodiff, so a scope is one component
anywhere in an op's ``op_name`` path: the forward's
``jit(step_fn)/fwd_bwd/jvp()/while/body/closed_call/moe_experts/...``, the
recompute's ``.../checkpoint/rematted_computation/moe_experts/...`` and the
backward's ``.../transpose(jvp())/.../moe_experts/...`` all count.  The
innermost scope of the path names the op.  As in ``bench/phases.py``, a
fusion takes its root's ``op_name``, and an instruction with none the scope
of the nearest instruction that uses it; the instruction of a device op is
read from the compiled step's HLO, which ``phases.step_hlo`` rebuilds.

``of_run`` serves the readers of a traced run: device seconds in the window
per scope, averaged over the traced devices.  On a program without these
scopes no op carries one, and the readers read nothing.
"""

from __future__ import annotations

import re
from collections import defaultdict

from bench import phases, trace

MOE = ("moe_route", "moe_dispatch", "moe_experts", "moe_combine")
ATTN = ("attn_core",)
SCOPES = MOE + ATTN

_SCOPE = re.compile(r"(?:^|[/(])(%s)(?=$|[/)])" % "|".join(SCOPES))


def scope_of(op_name) -> str | None:
    """The innermost of ``SCOPES`` in an ``op_name`` path, or None."""
    found = _SCOPE.findall(op_name or "")
    return found[-1] if found else None


def hlo_scopes(text: str) -> dict:
    """Instruction -> (scope or None, fused computation or None), for every
    instruction of the compiled module."""
    comp, roots, ins = None, {}, {}
    users = defaultdict(list)
    for line in text.splitlines():
        m = phases._COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            continue
        m = phases._INSTR.match(line)
        if not m:
            continue
        name, _, rest = m.groups()
        on = re.search(r'op_name="([^"]*)"', rest)
        body = re.search(r"calls=%([\w.-]+)", rest)
        ins[name] = (on.group(1) if on else None,
                     body.group(1) if body else None)
        for operand in re.findall(r"%([\w.-]+)", rest.split(")", 1)[0]):
            users[operand].append(name)
        if phases._ROOT.match(line):
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
                return scope_of(named[0])
            front = [u for n in front for u in users[n] if u not in seen]
            seen.update(front)
        return None

    return {name: (scope_of(own[name]) if own[name] else by_use(name), body)
            for name, (_, body) in ins.items()}


def expert_mm_flops(run: dict, tokens: int) -> float:
    """The held experts' three matmuls in a training step, forward (2) and
    backward (4): 6 x 3 d f for each assignment a token makes to an expert
    held here, k held / E of its k, in every expert layer.  The experts'
    share of ``bench/flops.py``'s 6 N_active T; recomputation not counted."""
    layers = run["num_hidden_layers"]
    if "mlp_layer_types" in run:
        layers = run["mlp_layer_types"].count("sparse")
    held = run["num_experts"]
    total = run.get("num_experts_total", held)
    per_token = run["num_experts_per_tok"] * held / total
    d, f = run["hidden_size"], run["moe_intermediate_size"]
    return 6.0 * tokens * per_token * 3 * d * f * layers


def of_run(ctx):
    """Device seconds in the traced window per scope, or None where there is
    no trace to read; read once, kept on ``ctx``."""
    if not hasattr(ctx, "scopes"):
        log_dir = phases._trace_dir()
        ctx.scopes = _read(ctx, log_dir) if log_dir else None
    return ctx.scopes


def _read(ctx, log_dir: str):
    from bench import harness

    record = phases.extract(log_dir)
    t0, t1 = trace.window_of(record)
    ops = [op for op in record["device_ops"]
           if op[1] < t1 and op[1] + op[2] > t0
           and op[0].split(".")[0] not in trace.CONTAINERS]
    if not ops:
        return None
    hlo = hlo_scopes(phases.step_hlo(ctx.cell))
    strange = sorted({n for n, *_ in ops
                      if n not in hlo or hlo[n][1] != record["calls"][n]})
    if strange:
        harness.info(f"scopes: {len(strange)} device ops of the window differ "
                     f"from the rebuilt step, e.g. {strange[:3]}; not read")
        return None
    n_dev = max(len({op[3] for op in ops}), 1)
    seconds = defaultdict(float)
    for name, start, dur, _ in ops:
        scope = hlo[name][0]
        if scope:
            seconds[scope] += (min(start + dur, t1) - max(start, t0)) / 1e9 / n_dev
    steps = max(ctx.steps, 1)
    harness.info("model scopes (ms a step): " + ", ".join(
        f"{k} {1e3 * seconds[k] / steps:.3f}" for k in SCOPES if k in seconds))
    return dict(seconds)
