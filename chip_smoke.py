"""Smoke run of the training path on one TPU chip.

    python chip_smoke.py

Drives the in-process trainer (``repro.launch.train``, the ``inproc``
runtime) through the driver's own argument parser at the full width of
``lm-100m`` (12 layers, d768, 32k vocab, random weights from ``--seed``):

1. train 5 steps in ``isp`` mode and 5 in ``isp-pod --scheme topk`` mode:
   every loss finite, the step-1 loss near ln(vocab), every sent fraction
   in (0, 1], and parameters that moved;
2. rerun the first ``isp`` step on this process's CPU device and hold the
   chip's step-1 loss to it within ``LOSS_REL_TOL``;
3. compile and run the significance filter and the wire-pack codec
   kernels on the chip (not interpreted) and match their references bit
   for bit.

Any failed check raises, and the script exits non-zero.  It also exits
non-zero, printing no result, when JAX finds no TPU.  The lines before the
last are informative, from one run: they are not benchmark numbers.  The
last line is one JSON object naming the device.
"""

from __future__ import annotations

import json
import math
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# the reference phase runs on this process's CPU device: keep the CPU
# backend up beside the chip when the platform list is pinned
_platforms = os.environ.get("JAX_PLATFORMS")
if _platforms and "cpu" not in _platforms.split(","):
    os.environ["JAX_PLATFORMS"] = _platforms + ",cpu"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.launch.compile_cache import use_compile_cache  # noqa: E402

TRAIN_ARGS = ["--arch", "lm-100m", "--workers", "4", "--per-worker-batch",
              "4", "--seq", "512", "--steps", "5", "--log-every", "1",
              "--seed", "0"]
MODES = {"isp": ["--mode", "isp"],
         "isp-pod/topk": ["--mode", "isp-pod", "--scheme", "topk"]}
# params and activations are bf16, and the chip and the CPU order their
# reductions differently
LOSS_REL_TOL = 0.02
LEAF_SHAPE = (768, 2048)  # an lm-100m FF weight


def info(msg: str) -> None:
    print(f"info: {msg}", flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def train_phase(dev, name: str, extra: list[str]) -> float:
    """Train 5 steps in one mode; returns the step-1 loss."""
    from repro.launch.train import RUNTIMES, parse_args, resolve_arch
    from repro.models.transformer import LM

    args = parse_args(TRAIN_ARGS + extra)
    t0 = time.perf_counter()
    res = RUNTIMES["inproc"](args)
    wall = time.perf_counter() - t0
    hist = res["history"]
    losses = [h["loss"] for h in hist]
    sent = [h["sent_fraction"] for h in hist]
    check(len(hist) == args.steps, f"{name}: ran {len(hist)} steps")
    check(all(math.isfinite(l) for l in losses), f"{name}: losses {losses}")
    ln_v = math.log(resolve_arch(args.arch, args.smoke).vocab_size)
    check(abs(losses[0] - ln_v) <= 1.0,
          f"{name}: step-1 loss {losses[0]} is not within 1.0 of {ln_v}")
    check(all(0.0 < s <= 1.0 for s in sent),
          f"{name}: sent fractions {sent} outside (0, 1]")
    init = LM(resolve_arch(args.arch, args.smoke)).init(
        jax.random.PRNGKey(args.seed))
    moved = [bool(jnp.any(a != b)) for a, b in zip(
        jax.tree.leaves(init), jax.tree.leaves(res["params"]))]
    finite = all(bool(jnp.all(jnp.isfinite(p)))
                 for p in jax.tree.leaves(res["params"]))
    check(finite, f"{name}: non-finite parameters after step {args.steps}")
    check(any(moved), f"{name}: parameters unchanged after {args.steps} steps")
    info(f"{name}: losses {losses}")
    info(f"{name}: sent fractions {sent}")
    info(f"{name}: loss fell over {args.steps} steps: "
         f"{losses[-1] < losses[0]} (not required)")
    info(f"{name}: {sum(moved)}/{len(moved)} parameter leaves changed")
    info(f"{name}: first step s (includes compile) {hist[0]['step_s']}; "
         f"later step s {[h['step_s'] for h in hist[1:]]}; phase wall s {wall}")
    info(f"{name}: peak_bytes_in_use "
         f"{(dev.memory_stats() or {}).get('peak_bytes_in_use')}")
    del res, init
    return losses[0]


def reference_phase(chip_loss: float) -> None:
    """The first isp step again, on this process's CPU device."""
    from repro.launch.train import RUNTIMES, parse_args

    args = parse_args(TRAIN_ARGS + MODES["isp"] + ["--steps", "1"])
    t0 = time.perf_counter()
    with jax.default_device(jax.devices("cpu")[0]):
        res = RUNTIMES["inproc"](args)
    cpu_loss = res["history"][0]["loss"]
    rel = abs(chip_loss - cpu_loss) / abs(cpu_loss)
    info(f"cpu reference: step-1 loss chip {chip_loss} cpu {cpu_loss} "
         f"relative delta {rel} (tolerance {LOSS_REL_TOL}); "
         f"wall s {time.perf_counter() - t0}")
    check(rel <= LOSS_REL_TOL,
          f"step-1 loss {chip_loss} on the chip differs from {cpu_loss} on "
          f"the CPU by {rel:.4f} (relative), over {LOSS_REL_TOL}")


def kernel_phase() -> None:
    """Compiled significance filter and wire codec against references."""
    from repro.kernels import ops, ref, wire_pack
    from repro.kernels.significance import significance_filter
    from repro.wire import codec

    check(ops._auto_interpret(None) is False, "kernels would be interpreted")
    check(codec._interpret() is False, "the wire codec would interpret")
    t0 = time.perf_counter()
    rng = np.random.default_rng(0)
    u, r = (rng.standard_normal(LEAF_SHAPE, np.float32) * 1e-3
            for _ in range(2))
    x = rng.standard_normal(LEAF_SHAPE, np.float32) * 0.02
    v_t = np.float32(0.1)
    sig, res = jax.device_get(significance_filter(
        jnp.asarray(u), jnp.asarray(x), jnp.asarray(r), jnp.asarray(v_t),
        interpret=False))
    with jax.default_device(jax.devices("cpu")[0]):
        sig_ref, res_ref = jax.device_get(ref.significance_ref(u, x, r, v_t))
    check(np.array_equal(sig.view(np.uint32), sig_ref.view(np.uint32)),
          "significance kernel: sig differs from significance_ref")
    check(np.array_equal(res.view(np.uint32), res_ref.view(np.uint32)),
          "significance kernel: residual differs from significance_ref")
    nnz = int(np.count_nonzero(sig))
    info(f"significance kernel: {LEAF_SHAPE} leaf, {nnz} significant, "
         "bit-identical to significance_ref")
    check(0 < nnz < sig.size, f"significance kernel: degenerate nnz {nnz}")

    flat = sig.reshape(-1)
    check(int(wire_pack.wire_nnz(jnp.asarray(flat), interpret=False)) == nnz,
          "wire_nnz disagrees with numpy's count")
    target = x.reshape(-1)
    for quant in ("none", "bf16"):
        check(codec.resolve_impl("auto", flat.size, flat.dtype, quant)
              == "pallas", f"auto does not pick the kernel (quant={quant})")
        for scheme in ("dense", "sparse", "bitmap"):
            enc = {}
            for impl in ("numpy", "pallas"):
                meta, parts, resid = codec.encode_leaf(
                    flat, scheme=scheme, quant=quant, key="k",
                    with_residual=True, impl=impl)
                enc[impl] = (meta, b"".join(bytes(p) for p in parts), resid)
            (m_np, b_np, r_np), (m_pl, b_pl, r_pl) = enc["numpy"], enc["pallas"]
            what = f"wire_pack {scheme}/{quant}"
            check(m_np == m_pl, f"{what}: meta {m_pl} != {m_np}")
            check(b_np == b_pl, f"{what}: wire bytes differ")
            check(np.array_equal(r_np.view(np.uint32), r_pl.view(np.uint32)),
                  f"{what}: residual differs")
        out = {impl: codec.decode_add_leaf(target, m_pl, b_pl, impl=impl)
               for impl in ("numpy", "pallas")}
        check(np.array_equal(out["numpy"].view(np.uint32),
                             out["pallas"].view(np.uint32)),
              f"wire_unpack_add bitmap/{quant}: differs from numpy decode+add")
        info(f"wire codec quant={quant}: dense/sparse/bitmap bytes, metas and "
             "residuals bit-identical to numpy; unpack-add bit-identical")
    info(f"kernel phase wall s (includes compiles) {time.perf_counter() - t0}")


def main() -> None:
    cache_dir = use_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, JAX found platform {dev.platform!r}")
    info("lines starting 'info:' come from one run and are not benchmark "
         "numbers")
    info(f"device_kind {dev.device_kind!r}, {len(jax.devices())} device(s)")
    info(f"compile cache {cache_dir}")
    chip_loss = None
    for name, extra in MODES.items():
        loss1 = train_phase(dev, name, extra)
        if chip_loss is None:
            chip_loss = loss1
    reference_phase(chip_loss)
    kernel_phase()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
