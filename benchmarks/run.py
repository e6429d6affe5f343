"""Benchmark entrypoint — one module per paper table/figure + kernels +
roofline.  Prints ``name,us_per_call,derived`` CSV rows at the end.

  PYTHONPATH=src python -m benchmarks.run [--only table4,kernels,...]
"""

from __future__ import annotations

import argparse
import time

from repro.utils.compile_cache import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: table4,table5,table7,figs,kernels,fleet,roofline")
    args = ap.parse_args()
    enable_compile_cache()
    only = set(args.only.split(",")) if args.only else None

    suites = []
    if only is None or "kernels" in only:
        suites.append(("kernels", "benchmarks.kernel_bench"))
    if only is None or "fleet" in only:
        suites.append(("fleet", "benchmarks.fleet_bench"))
    if only is None or "table4" in only:
        suites.append(("table4", "benchmarks.table4_lstm"))
    if only is None or "table5" in only:
        suites.append(("table5", "benchmarks.table5_mlp"))
    if only is None or "table7" in only:
        suites.append(("table7", "benchmarks.table7_cloud"))
    if only is None or "figs" in only:
        suites.append(("figs", "benchmarks.figs_contributors"))
    if only is None or "roofline" in only:
        suites.append(("roofline", "benchmarks.roofline"))

    csv_rows = []
    for name, modname in suites:
        print(f"\n===== {name} =====", flush=True)
        t0 = time.time()
        mod = __import__(modname, fromlist=["run"])
        rows = mod.run(verbose=True)
        print(f"===== {name} done in {time.time()-t0:.1f}s =====", flush=True)
        for row in rows:
            tag, val, extra = row[0], row[1], row[-1]
            csv_rows.append((tag, val, extra))

    print("\nname,us_per_call,derived")
    for tag, val, extra in csv_rows:
        print(f"{tag},{val},{extra}")


if __name__ == "__main__":
    main()
