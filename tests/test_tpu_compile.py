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
import re
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


def test_held_expert_layer_compiles_for_v5e(one_chip, monkeypatch):
    """The Mellum2 cell's expert layer, forward and backward, at its shapes:
    16 of 64 experts of width 896 at d 2304, 16,384 tokens.  Its grouped
    matmuls lower to the TPU kernel in the tiles they run in."""
    from repro.configs import get_arch
    from repro.models.moe import held_apply, moe_defs
    from repro.models import params as pdefs

    # the layer picks the kernel or its interpreter by the backend it runs on
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = get_arch("mellum2-12b-a2.5b.ep4")
    p = jax.tree.map(
        lambda d: jax.ShapeDtypeStruct(d.shape, d.dtype, sharding=one_chip),
        moe_defs(cfg), is_leaf=pdefs.is_def)
    x = jax.ShapeDtypeStruct((2, 8192, 2304), jnp.bfloat16, sharding=one_chip)

    def loss(p_, x_):
        out, aux = held_apply(cfg, p_, x_)
        return jnp.sum(out.astype(jnp.float32)) + aux

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(p, x).compile()
    text = compiled.as_text()
    # three forward and, for each, two backward grouped matmuls
    assert text.count('custom_call_target="tpu_custom_call"') == 9
    mem = compiled.memory_analysis()
    assert 0 < mem.temp_size_in_bytes < HBM_BYTES


# the long-sequence cells' attention calls: (B, S, KV heads, group, window)
FLASH_SHAPES = {
    "phi4mini-16k": (2, 16384, 8, 3, None),
    "mellum2-8k-full": (2, 8192, 4, 8, None),
    "mellum2-8k-window": (2, 8192, 4, 8, 1024),
}


@pytest.mark.parametrize("case", sorted(FLASH_SHAPES))
def test_flash_core_compiles_for_v5e(one_chip, monkeypatch, case):
    """The flash core's forward and backward at the two long-sequence
    cells' shapes: three kernel calls (forward, dq, dkv), fewer temporaries
    than the XLA core it replaces, and every kernel call inside the scope
    ``attn_core``, read whole (the kernel prints its op_name on a
    continuation line) as the ``attn_roofline`` reader reads it, but not
    as the line reader of ``attn_ms`` reads it."""
    from bench import scopes
    from bench.metrics.attn_roofline import joined
    from repro.models import attention as att

    # the core picks the kernel or its interpreter by the backend it runs on
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    b, s, kh, g, window = FLASH_SHAPES[case]
    q = jax.ShapeDtypeStruct((b, s, kh, g, 128), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, s, kh, 128), jnp.bfloat16, sharding=one_chip)

    def grad_of(core):
        def loss(q_, k_, v_):
            return jnp.sum(core(q_, k_, v_).astype(jnp.float32))
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            q, kv, kv).compile()

    flash = grad_of(lambda q_, k_, v_: att._flash_core(q_, k_, v_, window))
    if window is None:
        xla = grad_of(lambda q_, k_, v_: att._chunked_core(
            q_, k_, v_, 0, True, None, None))
    else:
        xla = grad_of(lambda q_, k_, v_: att._banded_core(
            q_, k_, v_, 0, window))
    tmp = flash.memory_analysis().temp_size_in_bytes
    assert 0 < tmp < xla.memory_analysis().temp_size_in_bytes
    raw = flash.as_text()
    text = joined(raw)
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 3
    for line in calls:
        op_name = re.search(r'op_name="([^"]*)"', line).group(1)
        assert scopes.scope_of(op_name) == "attn_core", op_name
    names = [re.match(r"\s*(?:ROOT )?%([\w.-]+) = ", line).group(1)
             for line in calls]
    found = scopes.hlo_scopes(text)
    assert {found[n][0] for n in names} == {"attn_core"}
    # attn_ms reads the text a line at a time and so misses the kernel's
    # op_name: it takes the scope of a user, outside the core for the dkv
    # call.  This pins that known gap (PERF.md, section 7); it fails once
    # bench/scopes.py reads each instruction whole, and then attn_ms counts
    # all of the kernel and attn_roofline can read it from bench/scopes.py
    line_read = scopes.hlo_scopes(raw)
    assert {line_read[n][0] for n in names} != {"attn_core"}


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


@pytest.mark.parametrize("mode", ["bsp", "isp-pod"])
def test_train_step_compiles_on_a_v5e_2x2_mesh(one_chip, topo, mode):
    """The trainer's step for four chips of a described ``v5e:2x2``, its
    shardings carried by its arguments as ``launch.dryrun`` passes them:
    ``bsp`` with the batch over a 4-way ``data`` axis, and the ``isp-pod``
    ``topk`` step with the pod state and the batch over a 4-way ``pod``
    axis and the params replicated, whose sum over pods is an all-reduce."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro import optim
    from repro.core.isp import ISPConfig
    from repro.dist.compression import CompressionConfig
    from repro.launch.train import LM_8M, lift_pod, make_pod_step, make_step
    from repro.models.transformer import LM

    axis = "data" if mode == "bsp" else "pod"
    mesh = Mesh(np.array(topo.devices).reshape(4), (axis,))
    lm = LM(LM_8M)
    optimizer = optim.make("adam", 3e-4)
    params = jax.eval_shape(lm.init, jax.random.PRNGKey(0))
    opt0 = jax.eval_shape(optimizer.init, params)

    def placed(tree, spec):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=NamedSharding(mesh, spec)), tree)

    def per_pod(tree):
        return placed(jax.eval_shape(lambda t: lift_pod(t, 4), tree),
                      P("pod"))

    batch = placed({k: jax.ShapeDtypeStruct((16, 256), jnp.int32)
                    for k in ("tokens", "labels")}, P(axis))
    if mode == "bsp":
        step = make_step(lm, optimizer, None)
        args = (placed(params, P()), placed(opt0, P()), None, batch)
    else:
        step = make_pod_step(lm, optimizer, ISPConfig(v=0.7),
                             CompressionConfig(scheme="topk", budget=0.01), 4)
        args = (placed(params, P()), per_pod(opt0), per_pod(params), batch)
    compiled = step.lower(*args).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert 0 < total < HBM_BYTES, total
    if mode == "isp-pod":
        assert re.search(r"\ball-reduce(-start)?\(", compiled.as_text())


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
