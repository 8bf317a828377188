"""Readings that the limits of ``correct`` are set from, for one cell.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,... \
        [--control-seeds a,b,c] [--fault-seeds a,b,c] [--out DIR]

In one process, for each seed: the program's trainer runs the cell's
checked steps (and one more) through the same hooks as a run, its state is
freed, and the plain reference follows the same steps; the compared
numbers are printed.  For the control seeds the reference is also run in
the control's precision (``bench/reference/numerics.control``) and set
against the reference; for the fault seeds the program runs once more with
half of each batch left out.  Each seed's numbers are one JSON line, and
with ``--out`` the lines also go to ``<DIR>/<cell>.jsonl``.  The limits in
``bench/workloads/<cell>.json`` lie between the sound runs' largest
readings and the smallest readings of the control and the faults.

Needs the chip, like a run.  Not run by the benchmark's own runs.
"""

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))


def seeds(text: str) -> list[int]:
    return [int(s) for s in text.split(",") if s]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, required=True)
    ap.add_argument("--control-seeds", type=seeds, default=[])
    ap.add_argument("--fault-seeds", type=seeds, default=[])
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    from bench import compare, harness
    from bench.reference import numerics
    from repro.launch.compile_cache import use_compile_cache

    harness.require_chip(1)
    use_compile_cache()
    cell = harness.load_cell(args.workload)
    out = None
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        out = open(os.path.join(args.out, f"{args.workload}.jsonl"), "a")

    def emit(kind, seed, prog, ref, t0):
        values, where = compare.numbers(prog, ref)
        line = json.dumps({"cell": args.workload, "kind": kind, "seed": seed,
                           "numbers": values, "worst": where,
                           "loss": [float(x) for x in prog["loss"]],
                           "seconds": time.perf_counter() - t0})
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    for seed in sorted(set(args.seeds + args.control_seeds + args.fault_seeds)):
        t0 = time.perf_counter()
        prog = harness.program_side(harness.program_readings(cell, seed))
        ref = harness.reference_side(cell, seed)
        if seed in args.seeds:
            emit("sound", seed, prog, ref, t0)
        if seed in args.control_seeds:
            t0 = time.perf_counter()
            ctl = harness.reference_side(
                cell, seed, numerics.control(cell.run["param_dtype"]))
            emit("control", seed, ctl, ref, t0)
        if seed in args.fault_seeds:
            t0 = time.perf_counter()
            half = harness.program_side(
                harness.program_readings(cell, seed, fault="half_batch"))
            emit("half_batch", seed, half, ref, t0)
    if out:
        out.close()


if __name__ == "__main__":
    main()
