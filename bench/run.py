"""Run one cell of the benchmark once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cells, their metrics and bounds are in ``BENCHMARK.json`` at the root
of the checkout.  The run needs the TPU chips its cell asks for: with none,
it exits non-zero and prints no result.  It keeps JAX's compilation cache
where ``repro.launch.compile_cache`` says (``JAX_COMPILATION_CACHE_DIR``, or
``.jax_cache`` in the checkout).  The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
with ``--trace 1`` also ``breakdown``, and last ``checks``, each compared
number beside its limit; the same numbers are the last lines of standard
error.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# this file's own directory would shadow the standard library's ``trace``
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    from bench import harness

    result = harness.run(args.workload, args.seed, args.seconds,
                         bool(args.trace), t_process=T_PROCESS)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(f"correct {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
