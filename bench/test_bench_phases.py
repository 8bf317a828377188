"""Device time by phase and host gaps by loop span (``bench/phases.py``): a
made-up record with known answers, the records trimmed from chip runs
(``bench/traces``), and the readers of a traced run on the CPU."""

import time
from pathlib import Path

import pytest

from bench import harness, phases, trace
from repro.launch import train

RECORDED = Path(__file__).resolve().parent / "traces"
MS = 1_000_000
HLO = """\
HloModule jit_step_fn, entry_computation_layout={...}

%fused_computation.1 (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %mul.1 = f32[4]{0} multiply(f32[4]{0} %param_0, f32[4]{0} %param_0), metadata={op_name="jit(step_fn)/vmap(optimizer)/mul" stack_frame_id=3}
}

ENTRY %main.9 (p: f32[4], a: f32[4,4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %a = f32[4,4]{1,0} parameter(1)
  %fusion.1 = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop, calls=%fused_computation.1
  %dot.2 = f32[4,4]{1,0} dot(%a, %a), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(step_fn)/fwd_bwd/transpose(jvp())/while/body/dot_general" stack_frame_id=5}
  %sort.3 = f32[4]{0} sort(%p), dimensions={0}, metadata={op_name="jit(step_fn)/reduce_sum" stack_frame_id=6}
  %copy.4 = f32[4]{0} copy(%p)
  %broadcast.5 = f32[4]{0} broadcast(%c), dimensions={}
  %tuple.6 = (f32[4]{0}) tuple(%broadcast.5)
  ROOT %while.7 = (f32[4]{0}) while(%tuple.6), condition=%cond, body=%body, metadata={op_name="jit(step_fn)/fwd_bwd/transpose(jvp())/while" stack_frame_id=7}
}
"""


def _record():
    dev = "/device:TPU:0"
    return {
        "device_ops": [
            ["fusion.1", 0, 4 * MS, dev],
            ["dot.2", 4 * MS, 3 * MS, dev],
            # a loop spans its body's ops: never counted itself
            ["while.3", 0, 11 * MS, dev],
            ["sort.3", 8 * MS, 2 * MS, dev],
            ["copy.4", 10 * MS, 1 * MS, dev],
            ["fusion.1", 20 * MS, 4 * MS, dev],
            # outside the window: never counted
            ["dot.2", 40 * MS, 5 * MS, dev],
        ],
        "host_spans": [
            ["window_start", 0, 0], ["window_end", 30 * MS, 0],
            ["train.dispatch", 0, 1 * MS], ["train_step", 0, 1 * MS],
            ["train.sync", 1 * MS, 10 * MS],
            ["train.log", 11 * MS, 1 * MS],
            ["train.input", 12 * MS, 7 * MS], ["input_build", 13 * MS, 5 * MS],
            ["train.dispatch", 19 * MS, 1 * MS], ["train_step", 19 * MS, 1 * MS],
            ["train.sync", 20 * MS, 5 * MS],
        ],
        "phases": {n: p for n, (p, _, _) in phases.hlo_phases(HLO).items()},
    }


def test_the_phase_names_are_the_programs():
    assert phases.PHASES == train.PHASES
    assert phases.LOOP_SPANS == train.LOOP_SPANS
    assert phases.UPDATE == tuple(p for p in train.PHASES if p != "fwd_bwd")


def test_op_names_map_to_phases():
    h = phases.hlo_phases(HLO)
    # a fusion with no op_name takes its root's; vmap(x) reads as x
    assert h["fusion.1"] == ("optimizer", "fused_computation.1", ["optimizer"])
    # the backward stays under the scope that holds the value_and_grad
    assert h["dot.2"][0] == "fwd_bwd"
    # outside every scope; with no op_name and no user that has one
    assert h["sort.3"][0] == "unscoped" and h["copy.4"][0] == "unscoped"
    # with no op_name: the phase of the nearest user that has one
    assert h["broadcast.5"][0] == h["tuple.6"][0] == "fwd_bwd"
    assert phases.phase_of("jit(other)/fwd_bwd/dot_general") == "unscoped"


def test_device_time_by_phase_and_gaps_by_span():
    r = phases.reduce(_record())
    assert r["window_s"] == pytest.approx(0.030)
    # [0, 7], [8, 11] and [20, 24] ms
    assert r["busy_s"] == pytest.approx(0.014)
    assert r["device_phases"] == pytest.approx(
        {"optimizer": 0.008, "fwd_bwd": 0.003, "unscoped": 0.003})
    gaps = dict(r["idle_gaps"])
    # 7-8 ms lies in the trainer's sync; the 11-20 ms gap is shared among
    # the log, the pipeline's construction (train.input, 12-13 and 18-19
    # ms), the harness's input build inside it (the innermost span wins)
    # and the step call; of 24-30 ms, 1 ms is the sync's and 5 in no span
    assert gaps == pytest.approx(
        {"sync": 0.002, "log": 0.001, "input": 0.002, "input build": 0.005,
         "step call": 0.001, "other": 0.005})
    assert r["loop_spans"]["train.sync"] == pytest.approx(0.015)


@pytest.mark.parametrize("path", sorted(RECORDED.glob("*.json.gz")),
                         ids=lambda p: p.name)
def test_recorded_trace_reads_as_bench_trace_reads_it(path):
    rec = trace.load(str(path))
    old, new = trace.reduce(rec), phases.reduce(rec)
    assert new["window_s"] == old["window_s"]
    assert new["busy_s"] == pytest.approx(old["busy_s"], rel=1e-12)
    assert sum(new["device_phases"].values()) == pytest.approx(
        sum(s for _, s in trace.reduce(rec, top=10**9)["device_ops"]))
    assert sum(s for _, s in new["idle_gaps"]) == pytest.approx(
        sum(s for _, s in old["idle_gaps"]))


def test_recorded_phases_of_a_chip_run():
    rec = trace.load(str(RECORDED / "phases" / "starcoder2.isp.small-batch.json.gz"))
    r = phases.reduce(rec)
    ph = r["device_phases"]
    # two steps of the small-batch cell: the phases cover the busy time,
    # little of it unscoped, forward/backward and the update both large
    assert sum(ph.values()) == pytest.approx(r["busy_s"], rel=0.01)
    assert ph.get("unscoped", 0.0) <= 0.02 * r["busy_s"]
    assert ph["fwd_bwd"] > 0.3 * r["busy_s"]
    assert sum(ph.get(p, 0.0) for p in phases.UPDATE) > 0.2 * r["busy_s"]
    assert set(ph) <= {"fwd_bwd", "clip", "optimizer", "isp_split",
                       "sent_count", "unscoped"}
    # the device waits on the loop's sync and input build; what no span
    # names is the loop's glue between its spans
    gaps = dict(r["idle_gaps"])
    assert gaps["sync"] > 0 and gaps["input build"] > 0
    assert gaps.get("other", 0.0) < 0.01 * sum(gaps.values())
    assert set(r["loop_spans"]) == {"train.input", "train.dispatch",
                                    "train.sync", "train.log"}


def test_rebuilt_step_is_the_step_the_trainer_ran(monkeypatch):
    from bench import test_bench_control as control

    cell = control._cell("isp")
    ran = {}
    wrap = harness.Probe.wrap

    def keep(self, real, lm, seed):
        step = wrap(self, real, lm, seed)

        def first(*args):
            ran.setdefault("text", lambda: real.lower(*args).compile().as_text())
            return step(*args)

        return first

    monkeypatch.setattr(harness.Probe, "wrap", keep)
    harness.program_readings(cell, 2**31 + 3)
    trained = {n: v[1] for n, v in phases.hlo_phases(ran["text"]()).items()}
    rebuilt = {n: v[1] for n, v in
               phases.hlo_phases(phases.step_hlo(cell)).items()}
    assert trained == rebuilt


def test_readers_of_a_traced_run_on_the_cpu(monkeypatch):
    from bench import test_bench_control as control

    # the harness knows the peaks of chips only
    monkeypatch.setattr(harness, "peak_of",
                        lambda kind: {"hbm_bytes_per_s": 819e9,
                                      "bf16_flops_per_s": 197e12})

    names = ("fwd_bwd_ms", "update_ms", "update_roofline", "sync_ms")
    spec = {**control.SPEC, "per_layer": [
        {"name": n, "unit": "ms"} for n in names]}
    out = harness.run("smoke", 2**31 + 5, 0.5, True,
                      t_process=time.perf_counter(), chip=False, spec=spec,
                      cell=control._cell("isp"))
    # the CPU has no TPU plane: no device op to join to a phase, but the
    # trainer's loop spans are on the host plane
    assert set(out["metrics"]) == {"sync_ms"}
    assert 0 < out["metrics"]["sync_ms"]["value"] < 1e3


def test_rebuilt_step_carries_the_scopes_past_a_cached_build(tmp_path,
                                                           monkeypatch):
    import contextlib

    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    from bench import test_bench_control as control

    cell = control._cell("isp")
    saved = {k: getattr(jax.config, k) for k in (
        "jax_enable_compilation_cache", "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cc.reset_cache()
    try:
        # the same step built with no scopes fills the cache: its key is
        # the key of the scoped step, which differs only in debug info
        with monkeypatch.context() as m:
            m.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
            bare = phases.step_hlo(cell)
        assert not phases._SCOPED.search(bare)
        assert phases._SCOPED.search(phases.step_hlo(cell))
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        cc.reset_cache()
