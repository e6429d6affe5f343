#!/usr/bin/env python3
"""Where a fleet study's time goes, by the program's own spans and
protocol phases, on the chip.

    python3 benchmarks/chip/layers.py --workload mlp.paper-1 --seed 7 \
        --seconds 10 [--profiled-seconds 10] [--keep-trace DIR]

From the root of a checkout, on a machine with the cell's chips.  Set-up
as ``run.py``: the cell's world from the seed and one warm-up study.
Then, in one process:

1. studies back to back for ``--seconds``, each study's span tree kept
   (``RunResult.timeline``): per span path (``unpack/writeback``) the
   median milliseconds a study, the share of a study's wall time its
   top-level spans cover, the share of ``stage`` and of ``unpack`` their
   children cover, each span's counts, and the slowest study's tree;
2. one study under the profiler, inside the benchmark's annotation: the
   idle gaps labelled by the innermost span path
   (``spantrace.host_spans``), and the fleet program's device self time
   per protocol phase (``spantrace.phase_time``, the map from one more
   study compiled with ``TraceConfig(hlo_stats=True)``);
3. what a span costs with no profiler running (10^5 begin/finish pairs
   on a fresh ``Timeline``) and, with ``--profiled-seconds``, the
   sessions per second of a window run under a running profiler.

``--keep-trace DIR`` also writes the traced study's trace, gzipped, and
the phase map of the instructions it ran, as
``DIR/<cell>.xplane.pb.gz`` and ``DIR/<cell>.phases.json``.  One JSON
object on standard output.  It measures and decides nothing; the
benchmark's own runs never run it.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import bench  # noqa: E402


def span_paths(timeline) -> dict:
    """{path: (seconds, attrs)} of one study's spans; a path repeated
    in one study sums its seconds."""
    paths, out = [], {}
    for s in timeline.spans:
        path = s.name if s.parent is None else f"{paths[s.parent]}/{s.name}"
        paths.append(path)
        seconds, attrs = out.get(path, (0.0, {}))
        out[path] = (seconds + s.dur, {**attrs, **s.attrs})
    return out


def window(spec, method, seconds: float):
    """Studies back to back for ``seconds``: (rows, elapsed seconds),
    a row holding the study's wall time and span paths."""
    rows, t0 = [], time.perf_counter()
    while not rows or time.perf_counter() - t0 < seconds:
        result = bench.run_study(spec, method)
        rows.append({"wall_s": result.wall_s,
                     "spans": span_paths(result.timeline)})
    return rows, time.perf_counter() - t0


def child_seconds(spans: dict, parent: str) -> float:
    """Seconds of the direct children of span path ``parent`` (of the
    top-level spans where ``parent`` is empty)."""
    prefix = f"{parent}/" if parent else ""
    return sum(s for p, (s, _) in spans.items()
               if p.startswith(prefix) and p.count("/") == prefix.count("/"))


def span_report(rows) -> dict:
    paths = list(dict.fromkeys(p for r in rows for p in r["spans"]))
    ms = {p: 1e3 * statistics.median(r["spans"].get(p, (0.0, {}))[0]
                                     for r in rows) for p in paths}
    slowest = max(rows, key=lambda r: r["wall_s"])
    report = {
        "studies": len(rows),
        "wall_ms": 1e3 * statistics.median(r["wall_s"] for r in rows),
        "span_ms": ms,
        "top_cover": statistics.median(child_seconds(r["spans"], "")
                                       / r["wall_s"] for r in rows),
        "attrs": {p: a for p, (_, a) in rows[-1]["spans"].items() if a},
        "slowest": {"wall_ms": 1e3 * slowest["wall_s"],
                    "span_ms": {p: 1e3 * s for p, (s, _)
                                in slowest["spans"].items()}}}
    for parent in ("stage", "unpack"):
        kids = {p: v for p, v in ms.items()
                if p.startswith(f"{parent}/") and p.count("/") == 1}
        report[f"{parent}_cover"] = statistics.median(
            child_seconds(r["spans"], parent) / r["spans"][parent][0]
            for r in rows)
        report[f"{parent}_largest"] = max(kids, key=kids.get)
    return report


def traced(cell, spec, method, keep: Path = None) -> dict:
    """One study under the profiler: its gaps by span path and the
    program's device time by phase."""
    import jax
    import devtrace
    import spantrace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(bench.ANNOTATION):
                result = bench.run_study(spec, method)
        finally:
            jax.profiler.stop_trace()
        path = devtrace.xplane_file(d)
        profile = jax.profiler.ProfileData.from_file(path)
        names = {s.name for s in result.timeline.spans}
        spans = spantrace.host_spans(profile, bench.ANNOTATION, names)
        reduced = devtrace.reduce(profile, bench.ANNOTATION, spans)
        raw = Path(path).read_bytes()
    phases = spantrace.phase_map({"model": cell.model, "conf": cell.conf,
                                  "traffic": cell.traffic})
    seconds = spantrace.phase_time(reduced, phases)
    program = sum(seconds.values())
    gaps = reduced["gaps"]
    if keep is not None:
        keep.mkdir(parents=True, exist_ok=True)
        (keep / f"{cell.name}.xplane.pb.gz").write_bytes(gzip.compress(raw))
        ran = {op["instr"] for op in reduced["ops"].values()
               if op["module"] == spantrace.PROGRAM}
        (keep / f"{cell.name}.phases.json").write_text(json.dumps(
            {k: v for k, v in sorted(phases.items()) if k in ran}))
    idle = {}
    for label, s in gaps:
        idle[label] = idle.get(label, 0.0) + s
    return {
        "window_ms": 1e3 * reduced["window_s"],
        "busy_ms": 1e3 * reduced["busy_s"],
        "idle_ms_by_span": {k: 1e3 * v for k, v in sorted(
            idle.items(), key=lambda kv: -kv[1])},
        "longest_gaps_ms": [[n, 1e3 * s] for n, s in
                            sorted(gaps, key=lambda g: -g[1])[:10]],
        "outside_inner_gaps_ms": [1e3 * s for n, s in gaps[1:-1]
                                  if n == "outside" and s >= 1e-3],
        "program_device_ms": 1e3 * program,
        "phase_device_ms": {k: 1e3 * v for k, v in sorted(
            seconds.items(), key=lambda kv: -kv[1])},
        "other_share": seconds.get("other", 0.0) / program if program else None}


def span_cost_us(pairs: int = 100_000) -> float:
    """Microseconds of one ``begin``/``finish`` pair on a fresh
    ``Timeline``, in the calling state of the profiler."""
    from repro.telemetry import Timeline
    tl = Timeline()
    t0 = time.perf_counter()
    for _ in range(pairs):
        tl.finish(tl.begin("x"))
    return 1e6 * (time.perf_counter() - t0) / pairs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--profiled-seconds", type=float, default=0.0)
    ap.add_argument("--keep-trace", type=Path, default=None)
    args = ap.parse_args(argv)
    spec_json = bench.load_json(bench.ROOT / "BENCHMARK.json")
    cell = bench.Cell(spec_json, args.workload)
    try:
        devices = bench.check_chips(cell.entry["chips"])
    except bench.NoChip as e:
        print(f"layers.py: {e}", file=sys.stderr)
        return 2
    bench.enable_compile_cache()
    spec, method, _ = bench.build(cell, args.seed)
    bench.run_study(spec, method)                    # compiles, warms up
    setup_s = time.perf_counter() - T_START
    rows, elapsed = window(spec, method, args.seconds)
    out = {"workload": cell.name, "seed": args.seed,
           "device": devices[0].device_kind, "setup_s": setup_s,
           "sessions_per_s": cell.traffic["requesters"] * len(rows) / elapsed,
           **span_report(rows),
           "trace": traced(cell, spec, method, args.keep_trace),
           "span_cost_us": span_cost_us()}
    if args.profiled_seconds:
        import jax
        with tempfile.TemporaryDirectory() as d:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(d, profiler_options=opts)
            try:
                out["profiled_span_cost_us"] = span_cost_us()
                prof, prof_s = window(spec, method, args.profiled_seconds)
            finally:
                jax.profiler.stop_trace()
        out["profiled_sessions_per_s"] = (cell.traffic["requesters"]
                                          * len(prof) / prof_s)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
