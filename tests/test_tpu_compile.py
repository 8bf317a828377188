"""Compile for a described TPU v5e and check the chip-facing entry points.

The kernels of the main path and the ``lm-100m`` ``isp`` train step are
compiled at real widths for one chip of a ``v5e:2x2`` topology that is
described, not attached: this catches what interpret mode cannot (block
shapes the TPU lowering refuses, VMEM overruns, programs that do not fit
the chip) at no chip time.  A compile that passes is not a chip run.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.

The CPU tests at the end cover the compile-cache placement and
``chip_smoke.py``'s refusal to run without a TPU.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
HBM_BYTES = 16 * 2**30  # one v5e chip
N_LEAF = 768 * 2048  # an lm-100m FF weight, flattened


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One chip's sharding, with the persistent compile cache off: a
    compile for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _kernel_case(name: str, chip):
    """(fn, abstract args) of one kernel at the width it runs at."""
    from repro.kernels import wire_pack as wp
    from repro.kernels.flash_attention import flash_attention_bhsd
    from repro.kernels.fused_adam import adam_sig_update, adam_update
    from repro.kernels.significance import significance_filter
    from repro.kernels.slstm_scan import slstm_scan

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    leaf = s((768, 2048))
    rows = wp.pick_block_rows(N_LEAF)
    cases = {
        "significance_filter": (
            lambda u, x, r, v: significance_filter(u, x, r, v),
            (leaf, leaf, leaf, s(()))),
        "adam_update": (
            lambda p, g, m, v: adam_update(p, g, m, v, 3e-4, 1),
            (leaf,) * 4),
        "adam_sig_update": (
            lambda p, g, m, v, r: adam_sig_update(p, g, m, v, r, 3e-4, 1, 0.7),
            (leaf,) * 5),
        "flash_attention_bhsd": (
            lambda q, k, v: flash_attention_bhsd(q, k, v),
            (s((12, 2048, 128), jnp.bfloat16),) * 3),
        "wire_pack_f32": (
            lambda x: wp.wire_pack(x, vdt=np.dtype(np.float32),
                                   block_rows=rows),
            (s((N_LEAF,)),)),
        "wire_pack_bf16": (
            lambda x: wp.wire_pack(x, vdt=jnp.bfloat16, block_rows=rows),
            (s((N_LEAF,)),)),
        "wire_nnz": (
            lambda x: wp.wire_nnz(x, block_rows=rows),
            (s((N_LEAF,)),)),
        "wire_unpack_add": (
            lambda t, m, c: wp.wire_unpack_add(t, m, c, block_rows=rows),
            (s((N_LEAF,)), s(((N_LEAF + 7) // 8,), jnp.uint8),
             s((1 << 20,)))),
        # xlstm-1.3b width: d=2048 in 4 heads of 512
        "slstm_scan_d2048": (
            lambda xg, r: slstm_scan(xg, r, n_heads=4),
            (s((1, 128, 4 * 2048)), s((4, 512, 4 * 512)))),
    }
    return cases[name]


@pytest.mark.parametrize("name", [
    "significance_filter", "adam_update", "adam_sig_update",
    "flash_attention_bhsd", "wire_pack_f32", "wire_pack_bf16", "wire_nnz",
    "wire_unpack_add",
    pytest.param("slstm_scan_d2048", marks=pytest.mark.xfail(
        strict=True, raises=jax.errors.JaxRuntimeError,
        reason="VMEM runs out: the kernel holds the dense block-diagonal "
               "(d, 4d) f32 recurrent matrix (64 MiB at d=2048) as one "
               "block")),
])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, args = _kernel_case(name, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_lm100m_isp_step_fits_one_v5e(one_chip):
    from repro import optim
    from repro.core.isp import ISPConfig
    from repro.launch.train import LM_100M, make_step
    from repro.models.transformer import LM

    lm = LM(LM_100M)
    optimizer = optim.make("adam", 3e-4)
    params = jax.eval_shape(lm.init, jax.random.PRNGKey(0))
    opt0 = jax.eval_shape(optimizer.init, params)

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
            tree)

    # the chip_smoke.py shape: 4 workers x batch 4 x seq 512
    batch = {k: jax.ShapeDtypeStruct((16, 512), jnp.int32, sharding=one_chip)
             for k in ("tokens", "labels")}
    step = make_step(lm, optimizer, ISPConfig(v=0.7))
    compiled = step.lower(
        on_chip(params), on_chip(opt0), on_chip(params), batch).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert 0 < total < HBM_BYTES, total


# -- CPU: compile-cache placement and chip_smoke.py --------------------------


def _env(**over) -> dict:
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = str(REPO / "src")
    env["JAX_PLATFORMS"] = "cpu"
    env.update(over)
    return env


_CACHE_PROBE = """
import json, sys
from repro.launch.compile_cache import use_compile_cache
path = use_compile_cache()
import jax
if sys.argv[1] == "compile":
    jax.jit(lambda x: x * 3.0 + 1.0)(1.0).block_until_ready()
print(json.dumps({"path": path,
                  "config": jax.config.jax_compilation_cache_dir}))
"""


def _probe(env: dict, compile_: bool) -> dict:
    out = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE,
         "compile" if compile_ else "nocompile"],
        env=env, capture_output=True, text=True, timeout=120, check=True)
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_compile_cache_honours_env_dir(tmp_path):
    env = _env(JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    got = _probe(env, compile_=True)
    assert got == {"path": str(tmp_path), "config": str(tmp_path)}
    assert any(tmp_path.iterdir()), "no compiled entry was cached"


def test_compile_cache_defaults_to_fixed_checkout_dir():
    from repro.launch.compile_cache import DEFAULT_DIR

    want = str(REPO / ".jax_cache")
    assert str(DEFAULT_DIR) == want
    assert _probe(_env(), compile_=False) == {"path": want, "config": want}
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_chip_smoke_refuses_to_run_without_tpu(tmp_path, where):
    script = REPO / "chip_smoke.py"
    if where == "alone":
        script = Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
    env = _env()
    del env["PYTHONPATH"]
    out = subprocess.run(
        [sys.executable, str(script)], cwd=script.parent, env=env,
        capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    if where == "checkout":
        assert "needs a TPU" in out.stderr and "'cpu'" in out.stderr
