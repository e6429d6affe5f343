#!/usr/bin/env python3
"""Run one cell of the chip benchmark and print its result line.

    python3 benchmarks/chip/run.py --workload mlp.paper-1 --seed 7 \
        --seconds 10 --trace 0

From the root of a checkout, on a machine whose JAX sees the chips the
cell asks for.  Set-up builds the cell's world from the seed, runs one
warm-up study (compiling, or loading from ``<checkout>/.jax_cache``),
then runs studies back to back for ``--seconds``; the study running when
the time is up finishes and counts.  ``--trace 1`` then profiles one
more study.  Afterwards the sessions of the window's last study are
compared with the plain reference.  The last line of standard output is
the result as JSON; the numbers compared, each beside its limit, are the
last lines of standard error.  Exits 2, printing no result, where JAX
finds no TPU or fewer chips than the cell asks for.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import bench  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        spec = bench.load_json(bench.ROOT / "BENCHMARK.json")
        line, checks = bench.run_cell(spec, args.workload, args.seed,
                                      args.seconds, bool(args.trace), T_START)
    except bench.NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
