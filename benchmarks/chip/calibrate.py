#!/usr/bin/env python3
"""Readings that a cell's comparison limits are set from, on the chip.

    python3 benchmarks/chip/calibrate.py --workload lstm.paper-16 \
        --seeds 1,2,3,4,5,6,7,8,9,10,11,12 --control-seeds 1,2,3 \
        --faults state_unchanged,half_batch,eval_half --fault-seeds 1,2,3

For each seed, in one process: the cell's world and its compared
sessions in the plain reference at float32.  Against them, for each
``--seeds`` seed, one study through the timed path (``Experiment.run``;
the lower reading); for each ``--control-seeds`` seed, the reference
computed in bfloat16, put in the program's place (the control); for each
``--fault-seeds`` seed, one study with each of ``--faults`` planted in
the program (``faults.py``).  One JSON line per reading on standard
output, every number compared beside the committed limit.  The
benchmark's own runs never run this.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import bench  # noqa: E402


def ints(text: str):
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-seeds", default="")
    args = ap.parse_args(argv)
    import jax.numpy as jnp
    import faults
    import reference

    spec = bench.load_json(bench.ROOT / "BENCHMARK.json")
    cell = bench.Cell(spec, args.workload)
    try:
        bench.check_chips(cell.entry["chips"])
    except bench.NoChip as e:
        print(f"calibrate.py: {e}", file=sys.stderr)
        return 2
    bench.enable_compile_cache()
    ref_model = cell.model.Reference(cell.conf)
    seeds, controls = ints(args.seeds), ints(args.control_seeds)
    planted = [f for f in args.faults.split(",") if f]
    fault_seeds = ints(args.fault_seeds)

    def program(world_spec, method, sessions, fault=None):
        if fault is None:
            result = bench.run_study(world_spec, method)
        else:
            with faults.planted(fault):
                result = bench.run_study(world_spec, method)
        got = bench.program_sessions(result, sessions)
        del result
        return got

    for seed in sorted(set(seeds) | set(controls) | set(fault_seeds)):
        t0 = time.perf_counter()
        world_spec, method, plain = bench.build(cell, seed)
        sessions = bench.compared_sessions(cell.traffic, seed)
        want = reference.study(ref_model, plain, cell.conf, cell.traffic,
                               sessions, jnp.float32)
        rows = []
        if seed in seeds:
            rows.append(("program", None, program(world_spec, method, sessions)))
        if seed in fault_seeds:
            for fault in planted:
                rows.append(("fault", fault,
                             program(world_spec, method, sessions, fault)))
        del world_spec
        gc.collect()
        if seed in controls:
            rows.append(("control", None, reference.study(
                ref_model, plain, cell.conf, cell.traffic, sessions,
                jnp.bfloat16)))
        for kind, fault, got in rows:
            correct, failed, checks = bench.compare(got, want, cell.limits)
            print(json.dumps({
                "workload": args.workload, "seed": seed, "kind": kind,
                "fault": fault, "correct": correct, "failed": failed,
                "param_gap": checks["param_gap"]["value"],
                "acc_gap_rows": checks["acc_gap_rows"]["value"],
                "session_param_gaps": [bench.param_gap(g["params"], w["params"])
                                       for g, w in zip(got, want)],
                "session_acc_gaps": [bench.accuracy_gap(g, w)
                                     for g, w in zip(got, want)],
                "checks": checks, "s": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
