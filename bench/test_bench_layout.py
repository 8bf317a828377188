"""The harness's reading of a configuration file: the program's
architecture, the reference's layout and the work counts.

The existing configurations read exactly what they read before the
harness took layer patterns and experts (the numbers are written here);
a file whose layer pattern, window or experts differ from the program's
is refused with the key named.
"""

import dataclasses
import math
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench.reference import model
from bench.test_bench_reference import (EXPERT_RUN, EXPERTS, PATTERN,
                                        PATTERN_RUN, RUN, SEED, SWIGLU)
from repro.configs import get_smoke
from repro.models.config import (ArchConfig, BlockSpec, FF, Mixer,
                                 pattern_groups, uniform_groups)
from repro.models.transformer import LM

GLOBAL_GELU = BlockSpec(Mixer.GLOBAL_ATTN, FF.GELU, window=None,
                        rope_base=10000.0)
GLOBAL_SWIGLU = BlockSpec(Mixer.GLOBAL_ATTN, FF.SWIGLU, window=None,
                          rope_base=10000.0)
STARCODER2_LEAVES = [
    "['embed']['tok']", "['final_norm']['bias']", "['final_norm']['scale']",
    "['groups'][0]['b0']['ff']['b_down']", "['groups'][0]['b0']['ff']['b_up']",
    "['groups'][0]['b0']['ff']['w_down']", "['groups'][0]['b0']['ff']['w_up']",
    "['groups'][0]['b0']['mixer']['wk']", "['groups'][0]['b0']['mixer']['wo']",
    "['groups'][0]['b0']['mixer']['wq']", "['groups'][0]['b0']['mixer']['wv']",
    "['groups'][0]['b0']['norm1']['bias']",
    "['groups'][0]['b0']['norm1']['scale']",
    "['groups'][0]['b0']['norm2']['bias']",
    "['groups'][0]['b0']['norm2']['scale']"]
PHI4_LEAVES = [
    "['embed']['tok']", "['final_norm']['scale']",
    "['groups'][0]['b0']['ff']['w_down']",
    "['groups'][0]['b0']['ff']['w_gate']", "['groups'][0]['b0']['ff']['w_up']",
    "['groups'][0]['b0']['mixer']['wk']", "['groups'][0]['b0']['mixer']['wo']",
    "['groups'][0]['b0']['mixer']['wq']", "['groups'][0]['b0']['mixer']['wv']",
    "['groups'][0]['b0']['norm1']['scale']",
    "['groups'][0]['b0']['norm2']['scale']"]
# config: (the program's ArchConfig, the reference's leaves, parameters), as
# the harness read them when it took a uniform stack alone
CONFIGS = {
    "starcoder2-7b.l4": (ArchConfig(
        name="starcoder2-7b.l4", family="dense", d_model=4608, n_heads=36,
        n_kv_heads=4, d_ff=18432, vocab_size=49152,
        groups=(((GLOBAL_GELU,), 4),), norm="layernorm",
        sub_quadratic=False), STARCODER2_LEAVES, 1_094_888_448),
    "phi4-mini-3.8b.l2v8": (ArchConfig(
        name="phi4-mini-3.8b.l2v8", family="dense", d_model=3072, n_heads=24,
        n_kv_heads=8, d_ff=8192, vocab_size=25008,
        groups=(((GLOBAL_SWIGLU,), 2),), sub_quadratic=False),
        PHI4_LEAVES, 278_412_288),
}
# cell: (model FLOPs a step, the update's necessary bytes a step)
CELLS = {
    "starcoder2.isp.small-batch": (6755985653760.0, 19707992064),
    "phi4mini.isp-pod.topk": (6919569801216.0, 16704737280),
    "starcoder2.bsp.large-batch": (54743669932032.0, 15328438272),
}


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_existing_configurations_read_as_before(name):
    arch, leaves, n = CONFIGS[name]
    cfg = harness.load_json("configs", name)
    assert harness.program_arch(cfg) == arch
    run = harness.sizes(cfg)
    assert model.leaf_names(run, cfg["blocks"]) == leaves
    flat, _ = model._leaves(model.layout(run, cfg["blocks"]))
    assert sum(math.prod(l.shape) for l in flat) == n == cfg["n_params"]


@pytest.mark.parametrize("name", sorted(CELLS))
def test_existing_cells_count_as_before(name):
    cell = harness.load_cell(name)
    probe = SimpleNamespace(dispatch=[], t_start=0.0, t_end=1.0, input_s=[])
    ctx = harness.context(cell, probe, {}, {})
    # the same floats, bit for bit
    assert (ctx.flops_per_step, ctx.update_bytes_per_step) == CELLS[name]


@pytest.mark.parametrize("layers", [1, 2, 4, 7])
def test_a_period_of_one_block_is_a_uniform_stack(layers):
    assert pattern_groups((GLOBAL_SWIGLU,), layers) == \
        uniform_groups(GLOBAL_SWIGLU, layers)


def _pattern(**run):
    cfg = {"name": "smoke.pattern", "blocks": SWIGLU,
           "run": dict(RUN, **dict(PATTERN_RUN, **run))}
    base = dataclasses.replace(get_smoke("phi4-mini-3.8b"),
                               groups=((PATTERN, 1),))
    return cfg, base


def _experts(**run):
    cfg = {"name": "smoke.experts", "blocks": dict(SWIGLU, ff="moe_swiglu"),
           "run": dict(RUN, **dict(EXPERT_RUN, **run))}
    base = dataclasses.replace(get_smoke("phi3.5-moe-42b-a6.6b"), moe=EXPERTS)
    return cfg, base


def _drop(cfg, key):
    del cfg["run"][key]
    return cfg


S, F = model.SLIDING, model.FULL
REFUSED = {
    "not whole periods": (lambda: _pattern(
        num_hidden_layers=6, layer_types=[S, S, S, F, S, S]),
        "num_hidden_layers"),
    "another window": (lambda: _pattern(sliding_window=32), "sliding_window"),
    "no window": (lambda: (_drop(_pattern()[0], "sliding_window"),
                           _pattern()[1]), "sliding_window"),
    "another order": (lambda: _pattern(layer_types=[F, S, S, S] * 2),
                      "layer_types"),
    "no layer types": (lambda: (_drop(_pattern()[0], "layer_types"),
                                _pattern()[1]), "layer_types"),
    "all full": (lambda: _pattern(layer_types=[F] * 8), "layer_types"),
    "another theta": (lambda: _pattern(rope_theta=500000.0), "rope_theta"),
    "yarn": (lambda: _pattern(rope_parameters={
        S: {"rope_type": "default", "rope_theta": 10000.0},
        F: {"rope_type": "yarn", "rope_theta": 10000.0, "factor": 16}}),
        "rope_parameters"),
    "untied": (lambda: _pattern(tie_word_embeddings=False),
               "tie_word_embeddings"),
    "biases": (lambda: _pattern(attention_bias=True), "attention_bias"),
    "dense width": (lambda: _pattern(intermediate_size=256),
                    "intermediate_size"),
    "experts held": (lambda: _experts(num_experts=8), "num_experts"),
    "experts routed": (lambda: ({**_experts()[0],
                                 "reduced": {"num_experts": 64}},
                                _experts()[1]), "num_experts_total"),
    "experts a token": (lambda: _experts(num_experts_per_tok=4),
                        "num_experts_per_tok"),
    "expert width": (lambda: _experts(moe_intermediate_size=256),
                     "moe_intermediate_size"),
    "no expert width": (lambda: (_drop(_experts()[0], "moe_intermediate_size"),
                                 _experts()[1]), "moe_intermediate_size"),
    "a dense layer": (lambda: _experts(mlp_layer_types=["sparse", "dense"]),
                      "mlp_layer_types"),
    "gates not renormalised": (lambda: _experts(norm_topk_prob=False),
                               "norm_topk_prob"),
    "no aux coefficient": (lambda: (_drop(_experts()[0],
                                          "router_aux_loss_coef"),
                                    _experts()[1]), "router_aux_loss_coef"),
    # the program keeps ceil(n k cf / E) slots an expert: 1.25 x 2 < 16
    "drops assignments": (lambda: (_experts()[0], dataclasses.replace(
        _experts()[1], moe=dataclasses.replace(EXPERTS, capacity_factor=1.25))),
        "dropless"),
    # the published starcoder2's window, on the program's global attention
    "a window on a global stack": (lambda: (
        {"name": "smoke.window", "blocks": SWIGLU,
         "run": dict(RUN, num_hidden_layers=2, sliding_window=4096)},
        get_smoke("phi4-mini-3.8b")), "layer_types"),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_a_file_the_program_cannot_honour_is_refused(case):
    make, key = REFUSED[case]
    cfg, base = make()
    with pytest.raises(ValueError, match=rf"\b{key}\b"):
        harness.program_arch(cfg, base)


def test_a_superblock_of_two_periods_is_refused():
    cfg, base = _pattern()
    base = dataclasses.replace(base, groups=((PATTERN * 2, 1),))
    with pytest.raises(ValueError, match="layer_types"):
        harness.program_arch(cfg, base)


@pytest.mark.parametrize("make", [_pattern, _experts])
def test_the_program_is_cut_to_the_file(make):
    cfg, base = make(mlp_layer_types=["sparse"] * 2) if make is _experts \
        else make()
    arch = harness.program_arch(cfg, base)
    layers = cfg["run"]["num_hidden_layers"]
    period = base.groups[0][0]
    assert arch.groups == ((period, layers // len(period)),)
    assert arch.n_layers == layers and arch.vocab_size == 300


def test_the_aux_coefficient_is_the_programs():
    cfg, base = _experts()
    lm = LM(harness.program_arch(cfg, base))
    params = lm.init(jax.random.PRNGKey(SEED))
    toks = jax.random.randint(jax.random.PRNGKey(0), (2, 16), 0, 300)
    total, m = lm.train_loss(params, {"tokens": toks, "labels": toks})
    assert float(m["moe_aux"]) > 0
    want = np.float32(m["xent"]) + np.float32(
        harness.PROGRAM_AUX_COEF) * np.float32(m["moe_aux"])
    np.testing.assert_allclose(np.float32(total), want, rtol=1e-6)


def test_the_gates_are_renormalised_as_the_programs():
    """The program's expert layer gives the reference's output where the
    reference renormalises the top-k gates, and not where it does not."""
    from repro.models.moe import moe_apply

    from bench.reference import moe_swiglu, numerics

    cfg, base = _experts()
    arch = harness.program_arch(cfg, base)
    ff = LM(arch).init(jax.random.PRNGKey(SEED))["groups"][0]["b0"]["ff"]
    p = jax.tree.map(lambda a: a[0].astype(jnp.float32), ff)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        prog, _ = moe_apply(arch, p, x)
    run, num = harness.sizes(cfg), numerics.of("float32")
    assert harness.PROGRAM_NORM_TOPK_PROB
    gap = {}
    for norm in (True, False):
        ref, _ = moe_swiglu.apply(p, x, num, dict(run, norm_topk_prob=norm))
        gap[norm] = float(jnp.max(jnp.abs(prog - ref)) / jnp.max(jnp.abs(ref)))
    assert gap[True] < 1e-5 and gap[False] > 0.1, gap


def test_leaves_keep_their_own_dtype():
    cfg, _ = _experts()
    run = harness.sizes(cfg)
    stores = dict(zip(model.leaf_names(run, cfg["blocks"]),
                      model.leaf_stores(run, cfg["blocks"],
                                        jnp.dtype("bfloat16"))))
    router = "['groups'][0]['b0']['ff']['router']"
    assert stores.pop(router) == jnp.float32
    assert set(stores.values()) == {jnp.dtype("bfloat16")}
