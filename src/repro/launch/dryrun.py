import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("DRYRUN_XLA_FLAGS")
    or "--xla_force_host_platform_device_count=512"
)

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The lines above MUST stay the first executable statements — jax locks
the platform and the device count at first init, and the production
meshes need 512 host placeholder devices (on a TPU host too: the CPU pin
keeps the dry-run off the chip). Do NOT export this flag anywhere else
(smoke tests and benchmarks must see 1 device).

Per cell this driver:
  1. builds the mesh (16x16 single-pod or 2x16x16 multi-pod),
  2. resolves abstract params/optimizer/cache/batch structs + shardings
     (zero allocation — everything is ShapeDtypeStruct),
  3. jit-lowers the real step function (the same one the drivers run),
  4. compiles, prints memory_analysis() (proof-of-fit) and cost_analysis(),
  5. parses collective wire bytes from the optimized HLO,
  6. writes the roofline record to results/dryrun/<cell>.json.

Usage:
  python -m repro.launch.dryrun --arch phi4-mini-3.8b --shape train_4k
  python -m repro.launch.dryrun --all --mesh single
  python -m repro.launch.dryrun --arch mixtral-8x22b --shape train_4k \
      --mesh multi --mode isp-topk --budget 0.01
"""

import argparse
import json
import time
import traceback
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs import ARCH_NAMES
from repro.core.isp import ISPConfig
from repro.dist.compression import CompressionConfig
from repro.launch.hloanalysis import analyze_hlo
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import Roofline, model_flops, parse_collectives
from repro.launch.specs import build_cell, opt_state_defs
from repro.launch.steps import (
    make_decode_step,
    make_isp_train_step,
    make_prefill_step,
    make_train_step,
)
from repro.models import params as pdefs
from repro.models.config import SHAPES, shape_applicable
from repro.configs import get_arch
from repro import optim


def _shardings(mesh, specs):
    from jax.sharding import NamedSharding, PartitionSpec as P

    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def dataclasses_replace_policy_strip_pod(lm):
    """LM with 'pod' removed from every policy axis (for the ISP step's
    per-pod inner function, where 'pod' is shard_map-manual)."""
    import dataclasses

    def strip(ax):
        if ax == "pod":
            return None
        if isinstance(ax, tuple):
            kept = tuple(a for a in ax if a != "pod")
            return kept if len(kept) > 1 else (kept[0] if kept else None)
        return ax

    pol = lm.policy
    fields = {}
    for f in dataclasses.fields(pol):
        v = getattr(pol, f.name)
        if f.name in ("batch", "moe_group_ax", "kv_seq"):
            v = strip(v)
        fields[f.name] = v
    fields["moe_groups"] = (
        max(1, pol.moe_groups // lm.policy.mesh.shape.get("pod", 1))
        if pol.moe_groups > 1 else pol.moe_groups
    )
    return dataclasses.replace(lm, policy=type(pol)(**fields))


_ISP_SCHEMES = {"isp-dense": "dense", "isp-topk": "topk",
                "isp-bitmap": "bitmap"}


def lower_cell(
    arch_name: str,
    shape_name: str,
    multi_pod: bool,
    mode: str = "bsp",
    budget: float = 0.01,
    n_pods: Optional[int] = None,
):
    """Returns (lowered, compiled, cell, mesh). Raises on inapplicable.

    ``n_pods`` overrides the production mesh with an elastic pool size
    (``dist.elastic.mesh_shape_for`` at 16x16 chips per pod) — the shape a
    scaled-in job re-lowers for after an auto-tuner eviction.
    """
    from jax.sharding import PartitionSpec as P

    if n_pods is not None:
        from repro.dist.elastic import make_mesh_for

        mesh = make_mesh_for(n_pods, data=16, model=16)
        multi_pod = n_pods > 1
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    cell = build_cell(arch_name, shape_name, mesh)
    lm = cell.lm
    optimizer = optim.make("adam", 1e-3)

    p_structs = cell.param_structs()
    p_specs = cell.param_specs()
    b_shardings = _shardings(mesh, cell.batch_specs)

    if cell.shape.kind == "train":
        o_defs = opt_state_defs(cell.param_defs)
        o_structs = pdefs.to_struct(o_defs)
        o_specs = pdefs.to_specs(o_defs)
        if mode == "bsp":
            step = make_train_step(lm, optimizer)
            jitted = jax.jit(
                step,
                in_shardings=(
                    _shardings(mesh, p_specs),
                    _shardings(mesh, o_specs),
                    b_shardings,
                ),
                donate_argnums=(0, 1),
            )
            args = (p_structs, o_structs, cell.batch_structs)
        elif mode.startswith("isp"):
            assert multi_pod, "ISP mode compresses across the pod axis"
            n_pods = mesh.shape["pod"]
            scheme = _ISP_SCHEMES.get(mode, "dense")
            # inside shard_map over 'pod' the pod axis is MANUAL — the
            # model's sharding constraints must not mention it
            lm_inner = dataclasses_replace_policy_strip_pod(lm)
            step = make_isp_train_step(
                lm_inner, optimizer, mesh,
                ISPConfig(v=0.7),
                CompressionConfig(scheme=scheme, budget=budget),
            )
            lift = lambda d: pdefs.stack(d, n_pods)

            def podspec(defs):
                return jax.tree.map(
                    lambda x: type(x)(*(("pod",) + tuple(x)[1:])),
                    pdefs.to_specs(defs),
                    is_leaf=lambda s: isinstance(s, P),
                )

            o_defs_pod = lift(o_defs)
            r_defs_pod = lift(cell.param_defs)
            jitted = jax.jit(
                step,
                in_shardings=(
                    _shardings(mesh, p_specs),
                    _shardings(mesh, podspec(o_defs_pod)),
                    _shardings(mesh, podspec(r_defs_pod)),
                    b_shardings,
                ),
                donate_argnums=(0, 1, 2),
            )
            args = (
                p_structs,
                pdefs.to_struct(o_defs_pod),
                pdefs.to_struct(r_defs_pod),
                cell.batch_structs,
            )
        else:
            raise ValueError(mode)
    elif cell.shape.kind == "prefill":
        step = make_prefill_step(lm)
        c_structs = pdefs.to_struct(cell.cache_defs)
        c_specs = pdefs.to_specs(cell.cache_defs)
        jitted = jax.jit(
            step,
            in_shardings=(
                _shardings(mesh, p_specs),
                _shardings(mesh, c_specs),
                b_shardings,
            ),
            donate_argnums=(1,),
        )
        args = (p_structs, c_structs, cell.batch_structs)
    else:  # decode
        step = make_decode_step(lm)
        c_structs = pdefs.to_struct(cell.cache_defs)
        c_specs = pdefs.to_specs(cell.cache_defs)
        jitted = jax.jit(
            step,
            in_shardings=(
                _shardings(mesh, p_specs),
                _shardings(mesh, c_specs),
                b_shardings,
                None,
            ),
            donate_argnums=(1,),
        )
        args = (
            p_structs,
            c_structs,
            cell.batch_structs,
            jax.ShapeDtypeStruct((), jnp.int32),
        )

    with mesh:
        t0 = time.time()
        lowered = jitted.lower(*args)
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()
    timings = {"lower_s": t1 - t0, "compile_s": t2 - t1}
    return lowered, compiled, cell, mesh, timings


def analyze(compiled, cell, mesh, mode: str) -> dict:
    mem = compiled.memory_analysis()
    xla_cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    # trip-count-aware analysis: XLA's cost_analysis visits while bodies
    # ONCE, undercounting every scanned layer (launch/hloanalysis.py)
    chips = mesh.devices.size
    n_pods = dict(zip(mesh.axis_names, mesh.devices.shape)).get("pod", 1)
    cpp = chips // n_pods if n_pods > 1 else 0
    cost = analyze_hlo(hlo, chips_per_pod=cpp)
    mf = model_flops(cell.arch, cell.shape, cell.lm.n_active_params())
    rl = Roofline(
        arch=cell.arch.name,
        shape=cell.shape.name,
        mesh="x".join(str(s) for s in mesh.devices.shape),
        chips=chips,
        hlo_flops_per_chip=cost.flops,
        hlo_bytes_per_chip=cost.bytes,
        wire_bytes_per_chip=cost.wire_bytes,
        wire_bytes_dci_per_chip=cost.wire_bytes_dci,
        model_flops_total=mf,
        collectives={k: v for k, v in cost.collectives.items()},
        peak_vmem_bytes=float(getattr(mem, "temp_size_in_bytes", 0)),
        argument_bytes=float(getattr(mem, "argument_size_in_bytes", 0)),
    )
    rec = rl.to_dict()
    rec["mode"] = mode
    rec["collective_count"] = cost.collective_count
    rec["unknown_loops"] = cost.unknown_loops
    rec["xla_flops_per_chip_unscaled"] = float(xla_cost.get("flops", 0.0))
    rec["memory_analysis"] = {
        "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
        "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
        "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
        "alias_bytes": int(getattr(mem, "alias_size_in_bytes", 0)),
    }
    # proof-of-fit: per-chip live bytes = args + temps (aliased args reuse)
    live = (
        rec["memory_analysis"]["argument_bytes"]
        + rec["memory_analysis"]["temp_bytes"]
        - rec["memory_analysis"]["alias_bytes"]
    )
    rec["fits_hbm_16gb"] = bool(live < 16e9)
    rec["live_bytes_per_chip"] = int(live)
    return rec


def _save_hlo(out_dir: str, cell_id: str, hlo: str) -> None:
    try:
        import zstandard as zstd

        with open(os.path.join(out_dir, cell_id + ".hlo.zst"), "wb") as f:
            f.write(zstd.ZstdCompressor(level=6).compress(hlo.encode()))
    except Exception:
        pass


def _load_hlo(out_dir: str, cell_id: str) -> Optional[str]:
    path = os.path.join(out_dir, cell_id + ".hlo.zst")
    if not os.path.exists(path):
        return None
    import zstandard as zstd

    return zstd.ZstdDecompressor().decompress(open(path, "rb").read()).decode()


def reanalyze_cell(
    arch_name: str, shape_name: str, multi_pod: bool, mode: str,
    out_dir: str, n_pods: Optional[int] = None,
) -> Optional[dict]:
    """Recompute the roofline record from the CACHED optimized HLO — no
    recompilation (the analyzer evolves faster than the compiler does)."""
    mesh_tag = (
        f"pods{n_pods}" if n_pods is not None
        else ("multi" if multi_pod else "single")
    )
    cell_id = f"{arch_name}__{shape_name}__{mesh_tag}__{mode}"
    out_path = os.path.join(out_dir, cell_id + ".json")
    hlo = _load_hlo(out_dir, cell_id)
    if hlo is None or not os.path.exists(out_path):
        return None
    with open(out_path) as f:
        old = json.load(f)
    if old.get("status") != "ok":
        return old
    if n_pods is not None:
        from repro.dist.elastic import make_mesh_for

        mesh = make_mesh_for(n_pods, data=16, model=16)
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    n_pods = dict(zip(mesh.axis_names, mesh.devices.shape)).get("pod", 1)
    cpp = mesh.devices.size // n_pods if n_pods > 1 else 0
    cost = analyze_hlo(hlo, chips_per_pod=cpp)
    cell = build_cell(arch_name, shape_name, mesh)
    mf = model_flops(cell.arch, cell.shape, cell.lm.n_active_params())
    rl = Roofline(
        arch=cell.arch.name, shape=cell.shape.name,
        mesh="x".join(str(s) for s in mesh.devices.shape),
        chips=mesh.devices.size,
        hlo_flops_per_chip=cost.flops,
        hlo_bytes_per_chip=cost.bytes,
        wire_bytes_per_chip=cost.wire_bytes,
        wire_bytes_dci_per_chip=cost.wire_bytes_dci,
        model_flops_total=mf,
        collectives=dict(cost.collectives),
        peak_vmem_bytes=old.get("peak_vmem_bytes", 0.0),
        argument_bytes=old.get("argument_bytes", 0.0),
    )
    rec = rl.to_dict()
    for k in ("mode", "memory_analysis", "fits_hbm_16gb",
              "live_bytes_per_chip", "timings", "status",
              "xla_flops_per_chip_unscaled"):
        if k in old:
            rec[k] = old[k]
    rec["collective_count"] = cost.collective_count
    rec["unknown_loops"] = cost.unknown_loops
    with open(out_path, "w") as f:
        json.dump(rec, f, indent=1)
    print(f"[reanalyzed] {cell_id}: {rec['bottleneck']} "
          f"frac={rec['roofline_fraction']:.3f}")
    return rec


def run_cell(
    arch_name: str,
    shape_name: str,
    multi_pod: bool,
    mode: str,
    out_dir: str,
    budget: float = 0.01,
    force: bool = False,
    n_pods: Optional[int] = None,
) -> Optional[dict]:
    mesh_tag = (
        f"pods{n_pods}" if n_pods is not None
        else ("multi" if multi_pod else "single")
    )
    cell_id = f"{arch_name}__{shape_name}__{mesh_tag}__{mode}"
    os.makedirs(out_dir, exist_ok=True)
    out_path = os.path.join(out_dir, cell_id + ".json")
    if os.path.exists(out_path) and not force:
        with open(out_path) as f:
            rec = json.load(f)
        print(f"[cached] {cell_id}: {rec.get('bottleneck')}")
        return rec

    arch = get_arch(arch_name)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(arch, shape)
    if not ok:
        rec = {"arch": arch_name, "shape": shape_name, "mesh": mesh_tag,
               "mode": mode, "status": why}
        with open(out_path, "w") as f:
            json.dump(rec, f, indent=1)
        print(f"[skip] {cell_id}: {why}")
        return rec

    print(f"[lower+compile] {cell_id} ...", flush=True)
    try:
        lowered, compiled, cell, mesh, timings = lower_cell(
            arch_name, shape_name, multi_pod, mode, budget, n_pods
        )
        _save_hlo(out_dir, cell_id, compiled.as_text())
        rec = analyze(compiled, cell, mesh, mode)
        rec["status"] = "ok"
        rec["timings"] = timings
        mem = compiled.memory_analysis()
        print(f"  memory_analysis: args={mem.argument_size_in_bytes/1e9:.2f}GB "
              f"temps={mem.temp_size_in_bytes/1e9:.2f}GB "
              f"out={mem.output_size_in_bytes/1e9:.2f}GB "
              f"alias={mem.alias_size_in_bytes/1e9:.2f}GB", flush=True)
        print(f"  cost_analysis: flops/chip={rec['hlo_flops_per_chip']:.3e} "
              f"bytes/chip={rec['hlo_bytes_per_chip']:.3e} "
              f"wire/chip={rec['wire_bytes_per_chip']:.3e}")
        print(f"  terms: compute={rec['compute_term_s']*1e3:.2f}ms "
              f"memory={rec['memory_term_s']*1e3:.2f}ms "
              f"collective={rec['collective_term_s']*1e3:.2f}ms "
              f"-> {rec['bottleneck']} | roofline_frac={rec['roofline_fraction']:.3f}")
    except Exception as e:
        rec = {
            "arch": arch_name, "shape": shape_name, "mesh": mesh_tag,
            "mode": mode, "status": f"error: {type(e).__name__}: {e}",
            "traceback": traceback.format_exc()[-4000:],
        }
        print(f"  ERROR {cell_id}: {e}")
    with open(out_path, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=ARCH_NAMES)
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--mesh", choices=("single", "multi", "both"),
                    default="single")
    ap.add_argument("--mode", default="bsp",
                    choices=("bsp",) + tuple(_ISP_SCHEMES))
    ap.add_argument("--budget", type=float, default=0.01)
    ap.add_argument("--pods", type=int, default=None,
                    help="elastic pool size (overrides --mesh; 16x16 "
                         "chips per pod, pod axis dropped at 1)")
    ap.add_argument("--all", action="store_true",
                    help="run every applicable (arch x shape) cell")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--reanalyze", action="store_true",
                    help="recompute analysis from cached HLO, no recompile")
    args = ap.parse_args()

    meshes = {"single": [False], "multi": [True], "both": [False, True]}[
        args.mesh
    ]
    cells = []
    if args.all:
        for a in ARCH_NAMES:
            for s in SHAPES:
                cells.append((a, s))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    n_ok = n_skip = n_err = 0
    for a, s in cells:
        for mp in meshes:
            if args.reanalyze:
                rec = reanalyze_cell(a, s, mp, args.mode, args.out,
                                     args.pods)
                if rec is None:
                    print(f"[no cached hlo] {a} {s}")
                    continue
                st = rec.get("status", "?")
                n_ok += st == "ok"
                continue
            rec = run_cell(a, s, mp, args.mode, args.out, args.budget,
                           args.force, args.pods)
            st = (rec or {}).get("status", "?")
            if st == "ok":
                n_ok += 1
            elif st.startswith("skip"):
                n_skip += 1
            else:
                n_skip += st.startswith("skipped")
                n_err += st.startswith("error")
    print(f"done: ok={n_ok} skipped={n_skip} errors={n_err}")


if __name__ == "__main__":
    main()
