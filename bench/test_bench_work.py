"""Work counts: model FLOPs and the update phase's necessary bytes."""

import math

import pytest

from bench import flops, harness
from bench.reference import model
from repro.launch import roofline
from repro.models.config import ShapeConfig
from repro.models.transformer import LM

CELLS = ["starcoder2.isp.small-batch", "phi4mini.isp-pod.topk",
         "starcoder2.bsp.large-batch"]


def _leaves(cell):
    flat, _ = model._leaves(model.layout(cell.run, cell.cfg["blocks"]))
    return [(math.prod(l.shape), 2) for l in flat]


@pytest.mark.parametrize("name", CELLS)
def test_parameter_count_is_the_programs(name):
    cell = harness.load_cell(name)
    lm = LM(harness.program_arch(cell.cfg))
    n = sum(e for e, _ in _leaves(cell))
    assert n == lm.n_params() == cell.cfg["n_params"]


@pytest.mark.parametrize("name", CELLS)
def test_flops_agree_with_the_programs_accounting(name):
    cell = harness.load_cell(name)
    arch = harness.program_arch(cell.cfg)
    n = cell.cfg["n_params"]
    ours = flops.train_step_flops(cell.run, n, cell.rows, cell.wl["seq"])
    shape = ShapeConfig("cell", cell.wl["seq"], cell.rows, "train")
    assert ours == pytest.approx(roofline.model_flops(arch, shape, n), rel=1e-12)
    # 6 N T dominates; attention adds a share that grows with the sequence
    assert ours >= 6.0 * n * cell.tokens_per_step


def test_update_bytes_by_mode():
    leaves = [(1000, 2), (24, 2)]
    n = 1024
    count = lambda mode, workers=4: harness.load_module(
        harness.BENCH / "work" / f"{mode}.py").update_bytes(leaves, workers)
    assert count("bsp") == n * 2 * 7
    assert count("isp") == n * 2 * 9
    # the shared parameters once, four pods' state and gradients
    assert count("isp-pod") == n * 2 * (2 + 4 * 7)
    assert count("isp-pod", 1) == count("isp")
