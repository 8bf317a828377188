"""The Mellum2 configuration, its held-expert layer and the readers of the
model's scopes, at smoke width on the CPU.

The smoke configuration has the mechanisms of the cell at a size the CPU
runs: 4 of 16 experts held, 4 a token, three windowed layers and one full in
a period of four, and a sequence longer than the window.
"""

import json
import time

import pytest

from bench import compare, flops, harness, scopes
from bench.reference import model

S, F = model.SLIDING, model.FULL
CFG = {
    "name": "smoke.mellum2",
    "program": {"arch": "mellum2-12b-a2.5b.ep4", "smoke": True},
    "run": {"hidden_size": 64, "num_attention_heads": 4,
            "num_key_value_heads": 2, "head_dim": 32,
            "moe_intermediate_size": 32, "num_hidden_layers": 4,
            "layer_types": [S, S, S, F], "mlp_layer_types": ["sparse"] * 4,
            "num_experts": 4, "num_experts_per_tok": 4,
            "norm_topk_prob": True, "router_aux_loss_coef": 0.01,
            "sliding_window": 16,
            "rope_parameters": {
                S: {"rope_type": "default", "rope_theta": 500000},
                F: {"rope_type": "default", "rope_theta": 500000}},
            "tie_word_embeddings": False, "vocab_size": 256,
            "param_dtype": "bfloat16"},
    "blocks": {"mixer": "rope_gqa_causal_rows", "ff": "moe_swiglu",
               "norm": "rmsnorm"},
    "reduced": {"num_experts": 16},
}
# this small cell's own limits, set as the cells' are: sound runs read loss
# 1e-4, gradient 1e-2 and change 2e-2 at most over a few seeds
LIMITS = {"loss_gap": 1e-3, "grad_gap": 0.05, "change_gap": 0.1}
SPEC = {
    "workloads": [{"name": "smoke", "chips": 1}],
    "end_to_end": [{"name": "tokens_per_s", "unit": "tokens/s"},
                   {"name": "setup_s", "unit": "s"}],
    "per_layer": [],
}
FILE = "mellum2-12b-a2.5b.l8e16v4"


def _cell() -> harness.Cell:
    wl = {"config": CFG["name"], "mode": "isp", "optimizer": "adam",
          "lr": 3e-4, "clip": 1.0, "isp_v": 0.7, "workers": 2,
          "per_worker_batch": 2, "seq": 64, "warm_steps": 4,
          "limits": LIMITS}
    return harness.Cell("smoke", wl, CFG)


def test_the_scope_names_are_the_programs():
    from repro.models import attention, moe

    assert scopes.MOE == moe.SCOPES
    assert scopes.ATTN == attention.SCOPES


@pytest.mark.parametrize("op_name,scope", [
    ("jit(step_fn)/fwd_bwd/jvp()/while/body/closed_call/moe_experts/"
     "jit(gmm)/pallas_call", "moe_experts"),
    ("jit(step_fn)/fwd_bwd/transpose(jvp())/while/body/closed_call/"
     "checkpoint/rematted_computation/moe_dispatch/gather", "moe_dispatch"),
    ("jit(step_fn)/fwd_bwd/transpose(jvp())/while/body/checkpoint/"
     "attn_core/while/body/closed_call/checkpoint/attn_core/dot_general",
     "attn_core"),
    ("jit(step_fn)/vmap(fwd_bwd)/vmap(moe_route)/top_k", "moe_route"),
    ("jit(step_fn)/fwd_bwd/moe_combine_extra/add", None),
    ("jit(step_fn)/isp_split/mul", None),
    (None, None),
])
def test_op_names_map_to_scopes(op_name, scope):
    assert scopes.scope_of(op_name) == scope


def test_scopes_of_a_compiled_step():
    """Every scope reaches the compiled step of the smoke cell, in the
    forward and in the backward, through the layer scan and the remat."""
    from bench import phases

    text = phases.step_hlo(_cell())
    found = scopes.hlo_scopes(text)
    assert set(s for s, _ in found.values()) == set(scopes.SCOPES) | {None}
    names = {}
    for line in text.splitlines():
        m = phases._INSTR.match(line)
        if m and m.group(1) in found:
            names.setdefault(found[m.group(1)][0], []).append(line)
    for scope in scopes.SCOPES:
        lines = names[scope]
        assert any("transpose(jvp" in l for l in lines), scope
        assert any("/jvp(" in l and "transpose" not in l for l in lines), scope
    # no op outside the model carries a model scope
    assert not any("isp_split" in l for s in scopes.SCOPES for l in names[s])


def test_smoke_trains_as_the_reference():
    """The smoke configuration through ``train()``, against the plain
    reference's first three steps."""
    cell = _cell()
    seed = 2**31 + 11
    prog = harness.program_side(harness.program_readings(cell, seed))
    ref = harness.reference_side(cell, seed)
    values, _ = compare.numbers(prog, ref)
    assert compare.judge(values, LIMITS), values


def test_readers_of_a_traced_run_on_the_cpu(monkeypatch):
    monkeypatch.setattr(harness, "peak_of",
                        lambda kind: {"hbm_bytes_per_s": 819e9,
                                      "bf16_flops_per_s": 197e12})
    names = ("moe_ms", "expert_mm_roofline", "attn_ms")
    spec = {**SPEC, "per_layer": [{"name": n, "unit": "ms"} for n in names]}
    out = harness.run("smoke", 2**31 + 13, 0.5, True,
                      t_process=time.perf_counter(), chip=False, spec=spec,
                      cell=_cell())
    # the CPU has no TPU plane: no device op, nothing to read
    assert out["correct"], out["checks"]
    assert not set(out["metrics"]) & set(names)


def test_the_configuration_file():
    cfg = harness.load_json("configs", FILE)
    arch = harness.program_arch(cfg)
    from repro.models.transformer import LM

    lm = LM(arch)
    assert lm.n_params() == cfg["n_params"] == 1_077_057_792
    run = harness.sizes(cfg)
    assert run["num_experts_total"] == 64 and model.period(run) == 4
    # the catalog's keys at the top level, as run
    for key, value in cfg.items():
        if key in run:
            assert value == run[key], key
    assert set(cfg["reduced"]) == {"num_hidden_layers", "layer_types",
                                   "mlp_layer_types", "num_experts",
                                   "vocab_size", "rope_parameters"}
    assert cfg["reduced"]["rope_parameters"][F]["rope_type"] == "yarn"
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    entry = {c["name"]: c for c in spec["configs"]}[FILE]
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"])


@pytest.mark.parametrize("name", ["mellum2.isp.moe-8k", "smoke"])
def test_expert_flops_are_the_experts_share_of_the_count(name):
    cell = _cell() if name == "smoke" else harness.load_cell(name)
    run = cell.run
    flat, _ = model._leaves(model.layout(run, cell.cfg["blocks"]))
    dense = [l for l in flat if not l.expert]
    t = cell.tokens_per_step
    n_active = flops.active_params(run, flat)
    n_dense = flops.active_params(run, dense)
    want = 6.0 * (n_active - n_dense) * t
    assert scopes.expert_mm_flops(run, t) == pytest.approx(want, rel=1e-12)
    if name != "smoke":
        # 16,384 tokens, 8 of 64 routed, 16 held: 2,048 assignments an
        # expert, 3 x 2304 x 896 each, in 8 layers
        assert want == 6.0 * 16 * 2048 * 3 * 2304 * 896 * 8


@pytest.mark.parametrize("window", [None, 16])
def test_row_wise_attention_is_the_reference_attention(monkeypatch, window):
    """``rope_gqa_causal_rows`` gives ``rope_gqa_causal``'s output and
    gradients; its chunks cut small here, so that a row takes several."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench.reference import numerics, rope_gqa_causal, rope_gqa_causal_rows

    monkeypatch.setattr(rope_gqa_causal_rows, "SCORE_BYTES", 4 * 4 * 64 * 16)
    run = CFG["run"]
    keys = jax.random.split(jax.random.PRNGKey(5), 5)
    p = {k: jax.random.normal(key, shape, jnp.float32) / np.sqrt(shape[0])
         for key, (k, (shape, _)) in zip(
             keys, sorted(rope_gqa_causal.defs(run).items()))}
    x = jax.random.normal(keys[-1], (2, 64, run["hidden_size"]), jnp.float32)
    num = numerics.of("float32")

    def loss(mod):
        def f(p_, x_):
            y = mod.apply(p_, x_, num, run, rope_base=5e5, window=window)
            return jnp.sum(jnp.sin(y)), y
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1), has_aux=True))

    (_, want), g_want = loss(rope_gqa_causal)(p, x)
    (_, got), g_got = loss(rope_gqa_causal_rows)(p, x)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    for a, b in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want)):
        assert float(jnp.linalg.norm(b)) > 0
        np.testing.assert_allclose(a, b, rtol=1e-4,
                                   atol=1e-5 * float(jnp.max(jnp.abs(b))))
