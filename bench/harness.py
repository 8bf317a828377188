"""One run of one cell: the program's trainer, timed, traced and checked.

A cell (``bench/workloads/<name>.json``) names a configuration
(``bench/configs/<name>.json``), the trainer's mode and the traffic: the
workers, each worker's batch and the sequence length.  The run drives the
program's own entry, ``repro.launch.train.train``, through its own
argument parser, with two hooks of the harness's own:

* the configuration is registered under its name in the trainer's table of
  extra architectures;
* the step the trainer builds through its ``MODES`` registry is wrapped,
  so that each dispatch is stamped on the host clock and annotated for the
  profiler.  The wrapper opens the measured window at the first dispatch
  after the warm steps and closes it by raising ``WindowClosed`` at the
  first dispatch past the deadline; every step before it was already
  synced by the trainer's ``float(loss)``.  The input the trainer builds
  for each step is timed by wrapping its ``TokenPipeline``.

The first steps feed the check: their losses, the first clipped gradient
(read from Adam's first moment after step 1) and the weights' change after
the checked steps (read before the next step takes them).  Once the window
has closed, the peak memory has been read and the program's state is
freed, the plain reference in ``bench/reference`` follows the same steps
from the same seed, and ``bench/compare.py`` sets the two side by side.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import shutil
import statistics
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace
from typing import Optional

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
CHECK_STEPS = 3  # steps the reference follows
ADAM_B1 = 0.9


class WindowClosed(Exception):
    """Raised by the wrapped step at the first dispatch past the deadline."""


class NoChip(SystemExit):
    pass


def load_json(kind: str, name: str) -> dict:
    path = BENCH / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} {name!r}: {path} is missing")
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    if not path.is_file():
        raise FileNotFoundError(f"{path} is missing")
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}".replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark() -> dict:
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    wl: dict
    cfg: dict

    @property
    def run(self) -> dict:
        return self.cfg["run"]

    @property
    def rows(self) -> int:
        return self.wl["workers"] * self.wl["per_worker_batch"]

    @property
    def tokens_per_step(self) -> int:
        return self.rows * self.wl["seq"]

    @property
    def hyper(self) -> dict:
        wl = self.wl
        return {"mode": wl["mode"], "lr": wl["lr"], "clip": wl["clip"],
                "isp_v": wl.get("isp_v", 0.0), "pods": wl["workers"],
                "budget": wl.get("budget", 1.0), "block": wl.get("block", 128)}


def load_cell(name: str) -> Cell:
    wl = load_json("workloads", name)
    return Cell(name, wl, load_json("configs", wl["config"]))


# ---- the program side ---------------------------------------------------------


def program_arch(cfg: dict):
    """The program's ArchConfig for a configuration file: the program's own
    architecture with the file's depth and vocabulary; every width is held
    to the file's."""
    from repro.configs import get_arch, get_smoke
    from repro.models.config import uniform_groups

    run = cfg["run"]
    program = cfg["program"]
    # "smoke" names the program's own small same-family configuration, for
    # the CPU tests of the harness
    base = (get_smoke if program.get("smoke") else get_arch)(program["arch"])
    (spec,), _ = base.groups[0]
    arch = dataclasses.replace(
        base, name=cfg["name"], vocab_size=run["vocab_size"],
        groups=uniform_groups(spec, run["num_hidden_layers"]))
    held = {
        "hidden_size": arch.d_model, "num_attention_heads": arch.n_heads,
        "num_key_value_heads": arch.n_kv_heads,
        "head_dim": arch.resolved_head_dim, "intermediate_size": arch.d_ff,
        "rope_theta": spec.rope_base,
        "tie_word_embeddings": arch.tie_embeddings,
        "param_dtype": arch.param_dtype,
    }
    wrong = {k: (v, run[k]) for k, v in held.items() if v != run[k]}
    if wrong or arch.qkv_bias or arch.n_layers != run["num_hidden_layers"]:
        raise ValueError(f"{cfg['name']}: program differs from the file: {wrong}")
    return arch


def train_argv(cell: Cell, seed: int, steps: int) -> list[str]:
    wl = cell.wl
    argv = ["--arch", cell.cfg["name"], "--mode", wl["mode"],
            "--workers", str(wl["workers"]),
            "--per-worker-batch", str(wl["per_worker_batch"]),
            "--seq", str(wl["seq"]), "--optimizer", wl["optimizer"],
            "--lr", repr(wl["lr"]), "--isp-v", repr(wl.get("isp_v", 0.7)),
            "--steps", str(steps), "--log-every", str(10**9),
            "--seed", str(seed)]
    if "scheme" in wl:
        argv += ["--scheme", wl["scheme"], "--budget", repr(wl["budget"])]
    return argv


class Probe:
    """State of the wrapped step: stamps, the window and the check's readings."""

    def __init__(self, warm: int, seconds: Optional[float], on_open=None,
                 fault: Optional[str] = None):
        self.warm, self.seconds, self.on_open, self.fault = warm, seconds, on_open, fault
        self.calls = 0
        self.dispatch: list[float] = []
        self.t_start = self.t_end = None
        self.deadline = math.inf
        self.losses: list = []
        self.window_losses: list = []
        self.grad = self.change = None
        self.input_s: list[float] = []
        self.compiles_in_window = 0

    @property
    def in_window(self) -> bool:
        return self.t_start is not None and self.t_end is None

    def wrap(self, real, lm, seed: int):
        import jax
        import jax.numpy as jnp

        grad_fn = jax.jit(lambda mu: jnp.stack([
            jnp.sqrt(jnp.sum(jnp.square(m.astype(jnp.float32))))
            for m in jax.tree.leaves(mu)]) / (1.0 - ADAM_B1))
        # the key is an argument, not a constant: one program for every seed
        change_fn = jax.jit(lambda p, key: jnp.stack([
            jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)
                                        - b.astype(jnp.float32))))
            for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(lm.init(key)))]))
        if self.fault == "unchanged":
            loss_fn = jax.jit(lambda p, b: lm.train_loss(p, b)[0])
            inner = lambda p, o, r, b: (p, o, r, loss_fn(p, b),
                                        jnp.float32(1.0))
        elif self.fault == "half_batch":
            inner = lambda p, o, r, b: real(
                p, o, r, jax.tree.map(lambda x: x[: x.shape[0] // 2], b))
        else:
            inner = real

        def step(params, opt_state, residual, batch):
            k = self.calls
            if k == 1:
                self.grad = grad_fn(opt_state.mu).block_until_ready()
            if k == CHECK_STEPS:
                self.change = change_fn(
                    params, jax.random.PRNGKey(seed)).block_until_ready()
            if self.seconds is not None and k >= self.warm:
                if k == self.warm:
                    if self.on_open:
                        self.on_open()
                    self.t_start = time.perf_counter()
                    self.deadline = self.t_start + self.seconds
                elif time.perf_counter() >= self.deadline:
                    self.t_end = time.perf_counter()
                    raise WindowClosed
            t = time.perf_counter()
            if self.in_window:
                self.dispatch.append(t)
            with jax.profiler.StepTraceAnnotation("train_step", step_num=k):
                out = inner(params, opt_state, residual, batch)
            self.calls += 1
            if k < CHECK_STEPS:
                self.losses.append(out[3])
            elif self.in_window:
                self.window_losses.append(out[3])
            return out

        return step


def _hook(probe: Probe, seed: int):
    """Install the harness's hooks in the trainer; returns the undo."""
    import jax
    from repro.launch import train as tr

    saved_modes, saved_pipe = dict(tr.MODES), tr.TokenPipeline

    def wrap_mode(mode):
        def build(lm, opt, isp, comp, pool):
            return probe.wrap(mode.build_step(lm, opt, isp, comp, pool), lm, seed)
        return dataclasses.replace(mode, build_step=build)

    for name, mode in saved_modes.items():
        tr.MODES[name] = wrap_mode(mode)

    class TimedPipeline(saved_pipe):
        def next_batch(self, step):
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation("input_build"):
                out = super().next_batch(step)
            if probe.in_window:
                probe.input_s.append(time.perf_counter() - t)
            return out

    tr.TokenPipeline = TimedPipeline

    def undo():
        tr.MODES.clear()
        tr.MODES.update(saved_modes)
        tr.TokenPipeline = saved_pipe

    return undo


def program_readings(cell: Cell, seed: int, *, seconds: Optional[float] = None,
                     on_open=None, fault: Optional[str] = None) -> Probe:
    """Run the program's trainer on the cell.  With ``seconds``, it runs the
    warm steps and then a window of that length; without, it runs the
    checked steps and one more, which reads the change."""
    import jax
    from repro.launch import train as tr

    arch = program_arch(cell.cfg)
    tr._EXTRA[arch.name] = arch
    warm = cell.wl["warm_steps"]
    if warm <= CHECK_STEPS:
        raise ValueError(f"{cell.name}: warm_steps must exceed {CHECK_STEPS}")
    steps = 10**9 if seconds is not None else CHECK_STEPS + 1
    probe = Probe(warm, seconds, on_open, fault)

    def count(event, *a, **k):
        if probe.in_window and event in (
                "/jax/core/compile/backend_compile_duration",
                "/jax/compilation_cache/cache_hits"):
            probe.compiles_in_window += 1

    jax.monitoring.register_event_duration_secs_listener(count)
    jax.monitoring.register_event_listener(count)
    undo = _hook(probe, seed)
    try:
        tr.RUNTIMES["inproc"](tr.parse_args(train_argv(cell, seed, steps)))
    except WindowClosed:
        pass
    finally:
        undo()
        jax.monitoring.unregister_event_duration_listener(count)
        jax.monitoring.unregister_event_listener(count)
    gc.collect()
    return probe


def program_side(probe: Probe) -> dict:
    import numpy as np

    return {"loss": np.array([float(l) for l in probe.losses]),
            "grad": np.asarray(probe.grad, np.float64),
            "change": np.asarray(probe.change, np.float64)}


def reference_side(cell: Cell, seed: int, num=None) -> dict:
    import jax.numpy as jnp

    from bench import traffic
    from bench.reference import model, numerics

    run = cell.run
    num = num or numerics.of(run["param_dtype"])
    batches = [tuple(jnp.asarray(a) for a in traffic.batch(
        run["vocab_size"], cell.wl["seq"], cell.rows, seed, s))
        for s in range(CHECK_STEPS)]
    return model.readings(run, cell.cfg["blocks"], num, cell.hyper, seed,
                          batches)


# ---- metrics ---------------------------------------------------------------------


def quantile90(xs: list[float]) -> float:
    return statistics.quantiles(xs, n=10, method="inclusive")[-1]


def end_to_end(probe: Probe, cell: Cell, setup_s: float) -> dict:
    window = probe.t_end - probe.t_start
    steps = len(probe.dispatch)
    stamps = probe.dispatch + [probe.t_end]
    step_s = [b - a for a, b in zip(stamps, stamps[1:])]
    return {
        "tokens_per_s": steps * cell.tokens_per_step / window,
        "step_ms_p90": 1e3 * quantile90(step_s) if len(step_s) >= 2 else None,
        "setup_s": setup_s,
    }


def context(cell: Cell, probe: Probe, reduced: dict, peak: dict) -> SimpleNamespace:
    from bench import flops
    from bench.reference import model

    run = cell.run
    flat, _ = model._leaves(model.layout(run, cell.cfg["blocks"]))
    itemsize = {"bfloat16": 2, "float32": 4, "float16": 2}[run["param_dtype"]]
    leaves = [(math.prod(l.shape), itemsize) for l in flat]
    n_params = sum(n for n, _ in leaves)
    work = load_module(BENCH / "work" / f"{cell.wl['mode']}.py")
    return SimpleNamespace(
        cell=cell, trace=reduced, steps=len(probe.dispatch),
        window_s=probe.t_end - probe.t_start, input_s=probe.input_s,
        flops_per_step=flops.train_step_flops(run, n_params, cell.rows,
                                              cell.wl["seq"]),
        update_bytes_per_step=work.update_bytes(leaves, cell.wl["workers"]),
        peak=peak)


def peak_of(kind: str) -> dict:
    with open(BENCH / "peaks.json") as f:
        peaks = json.load(f)
    if kind not in peaks:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return peaks[kind]


# ---- one run -----------------------------------------------------------------------


def require_chip(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise NoChip(f"bench: needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return devs


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_process: float, chip: bool = True, fault: Optional[str] = None,
        spec: Optional[dict] = None, cell: Optional[Cell] = None) -> dict:
    """One run; returns the result line (a dict)."""
    import jax

    from bench import compare
    from bench import trace as tracing

    spec = spec or benchmark()
    entry = {w["name"]: w for w in spec["workloads"]}.get(workload)
    if entry is None:
        raise KeyError(f"{workload!r} is not a cell of BENCHMARK.json")
    devs = require_chip(entry["chips"]) if chip else jax.devices()
    dev = devs[0]
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    cell = cell or load_cell(workload)
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None

    def open_trace():
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation("window_start"):
            pass

    probe = program_readings(cell, seed, seconds=seconds,
                             on_open=open_trace if trace else None, fault=fault)
    setup_s = probe.t_start - t_process
    if trace:
        with jax.profiler.TraceAnnotation("window_end"):
            pass
        jax.profiler.stop_trace()
    mem = dev.memory_stats() or {}
    memory_peak = int(mem.get("peak_bytes_in_use", 0))
    e2e = end_to_end(probe, cell, setup_s)
    prog = program_side(probe)
    window_losses = [float(l) for l in probe.window_losses]
    steps = len(probe.dispatch)
    compiles = probe.compiles_in_window
    del probe.losses, probe.window_losses
    gc.collect()
    info(f"window: {steps} steps in {probe.t_end - probe.t_start:.3f} s; "
         f"compiles inside it: {compiles}; setup_s {setup_s:.2f}; "
         f"memory_peak_bytes {memory_peak}")

    t_ref = time.perf_counter()
    ref = reference_side(cell, seed)
    values, where = compare.numbers(prog, ref)
    info(f"reference: {time.perf_counter() - t_ref:.1f} s; program loss "
         f"{prog['loss'].tolist()} reference loss {ref['loss'].tolist()}; "
         f"worst leaves {where}")
    limits = cell.wl["limits"]
    finite = all(math.isfinite(l) for l in window_losses)
    correct = compare.judge(values, limits) and finite and compiles == 0

    names = [m["name"] for m in spec["end_to_end"]
             if workload in m.get("workloads", [workload])]
    units = {m["name"]: m["unit"] for m in spec["end_to_end"] + spec["per_layer"]}
    metrics = {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": entry["chips"], "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": steps,
              "failed": sum(not math.isfinite(l) for l in window_losses)}
    if trace:
        reduced = tracing.reduce(tracing.extract(trace_dir))
        ctx = context(cell, probe, reduced, peak_of(dev.device_kind))
        info(f"update phase: {ctx.update_bytes_per_step} necessary HBM bytes "
             f"a step ({cell.wl['mode']}); model FLOPs a step "
             f"{ctx.flops_per_step}")
        for m in spec["per_layer"]:
            if workload not in m.get("workloads", [workload]):
                continue
            value = load_module(BENCH / "metrics" / f"{m['name']}.py").read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": units[m["name"]]}
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        for n in names:
            if e2e.get(n) is not None:
                metrics[n] = {"value": e2e[n], "unit": units[n]}
    result["metrics"] = metrics
    result["device"] = device
    checks = {k: {"value": values[k], "limit": limits[k]} for k in limits}
    checks["window_compiles"] = {"value": compiles, "limit": 0}
    result["checks"] = checks
    return result


def info(msg: str) -> None:
    print(f"info: {msg}", file=sys.stderr, flush=True)
