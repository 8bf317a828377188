"""The trainer's own instrumentation: phase scopes in the compiled step and
loop spans on the profiler's clock.

``launch/train.py`` wraps each phase of a train step in ``jax.named_scope``
(``train.PHASES``) and each part of a loop iteration in a
``jax.profiler.TraceAnnotation`` (``train.LOOP_SPANS``).  A profile of the
chip joins its device ops to these phases by the HLO instructions'
``op_name``, and labels the device's idle gaps by the loop span they fall
in.
"""

from __future__ import annotations

import glob
import re

import jax
import jax.numpy as jnp
import pytest

from repro import optim
from repro.configs import get_smoke
from repro.core.isp import ISPConfig
from repro.dist.compression import CompressionConfig
from repro.launch import train as T
from repro.models.transformer import LM

PODS = 4
MODE_PHASES = {
    "bsp": {"fwd_bwd", "clip", "optimizer", "apply"},
    "isp": {"fwd_bwd", "clip", "optimizer", "isp_split", "sent_count"},
    "isp-pod": {"fwd_bwd", "clip", "optimizer", "exchange"},
}
# Work the step does outside every phase, by op_name.  The forward's
# loop-invariant pieces (the RoPE angles, the causal mask, a where's
# broadcast) are hoisted out of the layers' scan by JAX's partial
# evaluation with no name stack; the pod step's loss mean is outside the
# phases by design.  The mask's broadcast keeps the attention core's own
# scope (``attention.SCOPES``) when it is hoisted.
UNSCOPED_OK = {
    "jit(step_fn)/" + n for n in (
        "le", "sin", "cos", "pow", "mul", "iota", "neg", "div",
        "convert_element_type", "jit(_where)/broadcast_in_dim",
        "attn_core/jit(_where)/broadcast_in_dim", "reduce_sum")
}
CHECKED = ("fusion", "dot", "convolution", "reduce", "sort")
INSTR = re.compile(r"^\s*(ROOT )?%([\w.-]+) = .*? ([a-z][\w-]*)\((.*)$")
COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.-]+) ")


def phase_of(op_name: str) -> str:
    """The path component right after ``jit(step_fn)/``, ``vmap(x)`` read
    as ``x``; anything else is ``unscoped``."""
    head, _, rest = op_name.partition("jit(step_fn)/")
    if head or not rest:
        return "unscoped"
    first = rest.split("/", 1)[0]
    m = re.fullmatch(r"vmap\((.*)\)", first)
    first = m.group(1) if m else first
    return first if first in T.PHASES else "unscoped"


def device_ops(text: str) -> dict:
    """Instructions of the computations that run as device ops (not a
    fusion's body, not a reducer): name -> (opcode, op_name or None).  A
    fusion with no op_name of its own takes its root's."""
    comp, roots, called, ins = None, {}, set(), {}
    for line in text.splitlines():
        m = COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            continue
        m = INSTR.match(line)
        if not m:
            continue
        root, name, opcode, rest = m.groups()
        on = re.search(r'op_name="([^"]*)"', rest)
        body = re.search(r"calls=%([\w.-]+)", rest)
        called.update(re.findall(r"(?:calls|to_apply)=%([\w.-]+)", rest))
        ins[name] = (opcode, on.group(1) if on else None,
                     body.group(1) if body else None, comp)
        if root:
            roots[comp] = name

    def resolve(name):
        opcode, on, body, _ = ins[name]
        while on is None and body in roots:
            _, on, body, _ = ins[roots[body]]
        return opcode, on

    return {n: resolve(n) for n, v in ins.items() if v[3] not in called}


def _compiled_step(mode: str) -> str:
    lm = LM(get_smoke("starcoder2-7b"))
    opt = optim.make("adam", 3e-4)
    isp = ISPConfig(0.7) if mode != "bsp" else None
    comp = CompressionConfig("topk", 0.01) if mode == "isp-pod" else None
    step = T.MODES[mode].build_step(lm, opt, isp, comp, PODS)
    params = jax.eval_shape(lm.init, jax.random.PRNGKey(0))
    opt0, res0 = jax.eval_shape(opt.init, params), params
    if T.MODES[mode].pod:
        opt0, res0 = jax.eval_shape(lambda o, r: (T.lift_pod(o, PODS),
                                                  T.lift_pod(r, PODS)),
                                    opt0, res0)
    batch = {k: jax.ShapeDtypeStruct((PODS * 2, 32), jnp.int32)
             for k in ("tokens", "labels")}
    return step.lower(params, opt0, res0, batch).compile().as_text()


@pytest.fixture
def no_persistent_cache():
    """The persistent compile cache's key leaves out the op_names (JAX
    strips debug info before hashing), so it may serve an executable whose
    metadata predates the scopes: compile afresh."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.mark.parametrize("mode", sorted(MODE_PHASES))
def test_phase_scopes_reach_the_compiled_step(mode, no_persistent_cache):
    text = _compiled_step(mode)
    # every phase reaches the compiled step, if only inside a fusion whose
    # root is another phase's (bsp's apply fuses into Adam's update)
    phases = {phase_of(on) for on in re.findall(r'op_name="([^"]*)"', text)}
    assert MODE_PHASES[mode] <= phases, phases
    ops = device_ops(text)
    named = {n: (opc, on) for n, (opc, on) in ops.items() if on is not None}
    unscoped = sorted((n, on) for n, (opc, on) in named.items()
                      if opc in CHECKED and phase_of(on) == "unscoped"
                      and on not in UNSCOPED_OK)
    assert not unscoped, unscoped
    # what carries no op_name at all the compiler made (layout copies, the
    # CPU's bf16 converts, its tree reductions): none of it is a matmul or
    # a sort that a phase should have owned
    anonymous = sorted(n for n, (opc, on) in ops.items()
                       if on is None and opc in CHECKED)
    assert not [n for n in anonymous
                if ops[n][0] in ("dot", "convolution", "sort")], anonymous


def test_phase_names_are_read_as_the_profile_reads_them():
    assert phase_of("jit(step_fn)/vmap(optimizer)/mul") == "optimizer"
    assert phase_of(
        "jit(step_fn)/fwd_bwd/transpose(jvp())/while/body/dot_general"
    ) == "fwd_bwd"
    assert phase_of("jit(step_fn)/reduce_sum") == "unscoped"
    assert phase_of("jit(other)/fwd_bwd/dot_general") == "unscoped"
    assert len(set(T.PHASES)) == len(T.PHASES)


def _host_spans(log_dir: str) -> dict:
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    spans: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                spans.setdefault(e.name, []).append(
                    (int(e.start_ns), int(e.start_ns + e.duration_ns)))
    return {k: sorted(v) for k, v in spans.items()}


def test_loop_spans_are_on_the_profilers_clock(tmp_path):
    steps = 3
    args = T.parse_args([
        "--arch", "starcoder2-7b", "--smoke", "--mode", "isp",
        "--steps", str(steps), "--workers", "2", "--per-worker-batch", "2",
        "--seq", "32", "--log-every", "1",
        "--checkpoint-dir", str(tmp_path / "ckpt"), "--checkpoint-every", "2"])
    log_dir = str(tmp_path / "trace")
    jax.profiler.start_trace(log_dir)
    with jax.profiler.TraceAnnotation("trace_start"):
        pass
    try:
        T.train(args)
    finally:
        with jax.profiler.TraceAnnotation("trace_end"):
            pass
        jax.profiler.stop_trace()
    spans = _host_spans(log_dir)
    got = {n: spans.get(n, []) for n in T.LOOP_SPANS}
    assert {n: len(v) for n, v in got.items()} == {
        "train.input": steps, "train.dispatch": steps, "train.sync": steps,
        "train.log": steps, "train.checkpoint": 1, "train.scale_in": 0}
    order = ("train.input", "train.dispatch", "train.sync", "train.log")
    chain = [got[n][i] for i in range(steps) for n in order]
    # one after another, no overlap, step after step
    assert all(a[1] <= b[0] for a, b in zip(chain, chain[1:])), chain
    # the checkpoint of step 2 lies between its log and step 3's input
    (ckpt,) = got["train.checkpoint"]
    assert got["train.log"][1][1] <= ckpt[0] and ckpt[1] <= got["train.input"][2][0]
    (start,), (end,) = spans["trace_start"], spans["trace_end"]
    assert all(start[1] <= s and e <= end[0]
               for v in got.values() for s, e in v)
