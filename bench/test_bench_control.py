"""``correct`` separates: a sound run passes, the control and the faults fail.

At smoke width on the CPU, with the harness's look for a chip skipped, the
whole of a run (the trainer's window, the check's readings, the reference,
the comparison) is driven as on the chip.  The limits here are this small
cell's own, set the way the cells' limits are: sound runs read loss 6e-5,
gradient 3e-3 and change 1.5e-2 at most over a few seeds; the control
reads gradient and change 1.0, half of the batch reads loss 7e-3 and
gradient 0.14, and an unchanged state reads 1.0.
"""

import time

import pytest

from bench import compare, harness
from bench.reference import numerics

LIMITS = {"loss_gap": 6e-4, "grad_gap": 0.03, "change_gap": 0.05}
CFG = {
    "name": "smoke.starcoder2", "program": {"arch": "starcoder2-7b", "smoke": True},
    "run": {"hidden_size": 64, "num_attention_heads": 4,
            "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
            "num_hidden_layers": 2, "vocab_size": 256, "rope_theta": 10000.0,
            "tie_word_embeddings": True, "param_dtype": "bfloat16"},
    "blocks": {"mixer": "rope_gqa_causal", "ff": "gelu_ff", "norm": "layernorm"},
}
SPEC = {
    "workloads": [{"name": "smoke", "chips": 1}],
    "end_to_end": [{"name": "tokens_per_s", "unit": "tokens/s"},
                   {"name": "setup_s", "unit": "s"}],
    "per_layer": [],
}


def _cell(mode: str) -> harness.Cell:
    wl = {"config": CFG["name"], "mode": mode, "optimizer": "adam",
          "lr": 3e-4, "clip": 1.0, "isp_v": 0.7, "workers": 4,
          "per_worker_batch": 2, "seq": 32, "warm_steps": 4,
          "limits": LIMITS}
    return harness.Cell("smoke", wl, CFG)


def _run(mode: str, fault=None, seed: int = 2**31 + 9) -> dict:
    return harness.run("smoke", seed, 0.5, False, t_process=time.perf_counter(),
                       chip=False, fault=fault, spec=SPEC, cell=_cell(mode))


@pytest.mark.parametrize("mode", ["isp", "bsp"])
def test_sound_run_is_correct(mode):
    out = _run(mode)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["checks"]["window_compiles"]["value"] == 0
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"tokens_per_s", "setup_s"}


@pytest.mark.parametrize("fault,fails", [
    ("unchanged", ("grad_gap", "change_gap")),
    ("half_batch", ("loss_gap", "grad_gap")),
])
def test_fault_makes_the_run_incorrect(fault, fails):
    out = _run("isp", fault=fault)
    assert not out["correct"]
    for name in fails:
        c = out["checks"][name]
        assert c["value"] > c["limit"], (name, c)


@pytest.mark.parametrize("seed", [5, 2**31 + 17])
def test_control_is_not_correct(seed):
    cell = _cell("isp")
    ref = harness.reference_side(cell, seed)
    ctl = harness.reference_side(cell, seed,
                                 numerics.control(CFG["run"]["param_dtype"]))
    values, _ = compare.numbers(ctl, ref)
    assert not compare.judge(values, LIMITS), values
