"""Work counts: model FLOPs and the update phase's necessary bytes."""

import json
import math

import numpy as np
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
    flat, _ = model._leaves(model.layout(cell.run, cell.cfg["blocks"]))
    assert flops.active_params(cell.run, flat) == n
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


def test_flops_of_sliding_layers_and_experts_by_hand():
    """A Mellum2-shaped file (a fixture, at the catalog's widths): 8 layers
    in [S, S, S, F] x 2 with a window of 1,024; 16 of 64 experts, 8 a token,
    of width 896; an untied head of 24,576 rows; 4 x 2 x 4,096 tokens."""
    with open(harness.BENCH / "fixtures" / "mellum2-shaped.l8e16v4.json") as f:
        cfg = json.load(f)
    run = harness.sizes(cfg)
    assert model.period(run) == 4 and run["num_experts_total"] == 64
    flat, _ = model._leaves(model.layout(run, cfg["blocks"]))
    d, vp = 2304, 24_576
    attn = 2304 * 4096 * 2 + 2304 * 512 * 2
    layer = attn + 2 * d + d * 64  # projections, two norms, the router
    experts = 16 * 3 * d * 896
    # the layers, tok and unembed, the final norm
    dense = 8 * layer + 2 * vp * d + d
    assert sum(math.prod(l.shape) for l in flat) == dense + 8 * experts \
        == 1_077_057_792
    n_active = flops.active_params(run, flat)
    # the assignments a token makes to the held experts: 8 of 64 routed
    assert n_active == dense + 8 * experts * 8 / 64 == 383_424_768
    rows, seq = 8, 4096
    full = 2 * 3 * 4.0 * rows * 32 * (seq * seq / 2) * 128
    sliding = 6 * 3 * 4.0 * rows * 32 * (1024 * seq - 1024 ** 2 / 2) * 128
    assert full == pytest.approx(6.597e12, rel=1e-3)
    assert sliding == pytest.approx(8.659e12, rel=1e-3)
    total = flops.train_step_flops(run, n_active, rows, seq)
    assert total == 6.0 * n_active * rows * seq + full + sliding
    assert total == pytest.approx(90.64e12, rel=1e-3)
    # the count over every parameter with full attention would read 2.6x
    old = 6.0 * 1_077_057_792 * rows * seq + 8 * 3 * 4.0 * rows * 32 * seq \
        * (seq / 2) * 128
    assert old / total == pytest.approx(2.63, abs=0.01)


@pytest.mark.parametrize("seq,window", [(4096, 1024), (64, 16), (512, 512),
                                         (100, 7)])
def test_attended_pairs_count_the_mask(seq, window):
    """w S - w^2 / 2 is the sliding mask's count, as S^2 / 2 is the causal
    mask's, up to the diagonal's half row: both leave out the S/2 or w/2 of
    counting the diagonal whole; and at w = S the two agree."""
    q = np.arange(seq)[:, None]
    k = np.arange(seq)[None, :]
    exact = np.sum((k <= q) & (q - k < window))
    assert flops.attended_pairs(seq, window) == exact - window / 2
    assert flops.attended_pairs(seq) == np.sum(k <= q) - seq / 2
    assert flops.attended_pairs(seq, seq) == flops.attended_pairs(seq)
