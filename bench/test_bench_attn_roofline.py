"""``attn_roofline``: the attention term of the two long-sequence cells, the
reader's arithmetic, and its reading of a kernel whose op_name sits on a
continuation line of the HLO text."""

import time
from types import SimpleNamespace

import pytest

from bench import flops, harness, scopes
from bench.metrics import attn_roofline

PEAK = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


@pytest.mark.parametrize("name,want", [
    # 2 full layers x 3 x 4 x 2 rows x 24 heads x 16,384^2 / 2 pairs x 128
    ("phi4mini.bsp.long-seq", 2 * 12.0 * 2 * 24 * 16384**2 / 2 * 128),
    # 2 full layers at 8,192^2 / 2 pairs and 6 windowed at 1,024 x 8,192 -
    # 1,024^2 / 2, 32 heads
    ("mellum2.isp.moe-8k", 12.0 * 2 * 32 * 128 * (
        2 * 8192**2 / 2 + 6 * (1024 * 8192 - 1024**2 / 2))),
])
def test_attention_term_is_the_hand_count(name, want):
    cell = harness.load_cell(name)
    got = attn_roofline.attn_flops(cell)
    assert got == pytest.approx(want, rel=1e-12)
    # the attention term of the cell's whole count
    run = cell.run
    assert got == pytest.approx(
        flops.train_step_flops(run, 1.0, cell.rows, cell.wl["seq"])
        - 6.0 * cell.tokens_per_step, rel=1e-12)


@pytest.mark.parametrize("name,attn_ms,want", [
    ("phi4mini.bsp.long-seq", 3049.1, 19.79e12 / 197e12 / 3.0491 * 100),
    ("mellum2.isp.moe-8k", 1753.8, 11.24e12 / 197e12 / 1.7538 * 100),
])
def test_reader_arithmetic(name, attn_ms, want):
    """The attention term over the time a step under ``attn_core`` at the
    chip's bf16 peak: the parent's ``attn_ms`` reads ~3.3% in both cells."""
    steps = 7
    ctx = SimpleNamespace(cell=harness.load_cell(name), steps=steps,
                          peak=PEAK, attn_core_s=steps * attn_ms / 1e3)
    got = attn_roofline.read(ctx)
    assert got == pytest.approx(want, rel=2e-3)
    assert 3.2 < got < 3.4


def test_nothing_to_read():
    ctx = SimpleNamespace(cell=None, steps=7, peak=PEAK, attn_core_s=None)
    assert attn_roofline.read(ctx) is None


# A compiled module shaped as the splash kernel's backward prints it: the
# kernel's metadata is a JSON object over three lines, and the op_name
# comes after it.
HLO = '''HloModule jit_step_fn

%fused_computation (param_0: bf16[4]) -> f32[4] {
  %param_0 = bf16[4]{0} parameter(0)
  %convert.1 = f32[4]{0} convert(%param_0), metadata={op_name="jit(step_fn)/fwd_bwd/transpose(jvp())/checkpoint/attn_core/convert_element_type"}
  ROOT %convert.2 = f32[4]{0} negate(%convert.1), metadata={op_name="jit(step_fn)/fwd_bwd/transpose(jvp())/checkpoint/convert_element_type"}
}

ENTRY %main.1 (q: bf16[4]) -> f32[4] {
  %q = bf16[4]{0} parameter(0)
  %splash_mha_dq_no_residuals.1 = (f32[4]{0}, bf16[4]{0}) custom-call(%q), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={
"xprof_metadata":"{\\"block_q\\": 512, \\"block_kv\\": 512}"
}}, metadata={op_name="jit(step_fn)/fwd_bwd/transpose(jvp(attn_core))/vmap(jit(_splash_attention))/splash_mha_dq_no_residuals/pallas_call"}
  %gte.1 = bf16[4]{0} get-tuple-element(%splash_mha_dq_no_residuals.1), index=1, frontend_attributes={kernel_metadata={
"xprof_metadata":"{\\"block_q\\": 512, \\"block_kv\\": 512}"
}}, metadata={op_name="jit(step_fn)/fwd_bwd/transpose(jvp(attn_core))/vmap(jit(_splash_attention))/splash_mha_dq_no_residuals/pallas_call"}
  ROOT %convert_fusion = f32[4]{0} fusion(%gte.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step_fn)/fwd_bwd/transpose(jvp())/checkpoint/convert_element_type"}
}
'''


def test_a_kernel_is_read_by_its_own_op_name():
    text = attn_roofline.joined(HLO)
    assert len(text.splitlines()) == len(HLO.splitlines()) - 4
    read = scopes.hlo_scopes(text)
    assert set(read) == set(scopes.hlo_scopes(HLO))
    assert read["splash_mha_dq_no_residuals.1"][0] == "attn_core"
    assert read["gte.1"][0] == "attn_core"
    # its user's fusion is named by the root outside the scope
    assert read["convert_fusion"] == (None, "fused_computation")
    # a module with no continuation line is left as it was
    assert attn_roofline.joined(text) == text


def test_reader_of_a_traced_run_on_the_cpu(monkeypatch):
    """Through the harness's traced run on the CPU, which has no TPU plane:
    nothing to read, and no error."""
    from bench import test_bench_mellum2 as smoke

    monkeypatch.setattr(harness, "peak_of", lambda kind: PEAK)
    spec = {**smoke.SPEC,
            "per_layer": [{"name": "attn_roofline", "unit": "%"}]}
    out = harness.run("smoke", 2**31 + 17, 0.5, True,
                      t_process=time.perf_counter(), chip=False, spec=spec,
                      cell=smoke._cell())
    assert out["correct"], out["checks"]
    assert "attn_roofline" not in out["metrics"]
