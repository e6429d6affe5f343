"""The chip benchmark's harness: one cell of ``BENCHMARK.json`` from set-up
to its result line.

Everything that belongs to one configuration, traffic mix, cell or
metric is a file of its own, found by the name ``BENCHMARK.json`` gives:

* ``configs/<config>.json``: the configuration's sizes as run, and
  ``configs/<config>.py`` beside it: its data generator, how the program
  is given the model, the plain reference model and its forward FLOPs;
* ``traffic/<traffic>.json``: the study each window call runs (requesters,
  contributors, rounds, protocol knobs);
* ``cells/<cell>.json``: the limits of the comparison that decides
  ``correct``, with the readings each was set from;
* ``metrics/<metric>.py``: ``read(record)`` for one metric, returning
  ``None`` where the record holds nothing to read.

The window drives the path users run:
``repro.api.Experiment(world, method, ExecutionSpec(engine="fleet")).run()``,
one study after another in a closed loop, every setting at its default.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import statistics
import sys
import tempfile
import time
from pathlib import Path
from typing import List, Sequence

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
ANNOTATION = "bench_study"
HOST_SPANS = ("stage", "program", "unpack")


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


# ---- files found by name ---------------------------------------------------

def find(kind: str, name: str, ext: str, dirs: Sequence[Path] = (HERE,)) -> Path:
    for d in dirs:
        p = Path(d) / kind / f"{name}{ext}"
        if p.is_file():
            return p
    raise FileNotFoundError(f"no {kind}/{name}{ext} under {list(map(str, dirs))}")


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem.replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def cell_metrics(bench: dict, cell: str, kind: str) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


class Cell:
    """One workload's configuration, traffic and limits, loaded by name."""

    def __init__(self, bench: dict, name: str, dirs: Sequence[Path] = (HERE,)):
        self.name = name
        self.entry = workload(bench, name)
        self.conf = load_json(find("configs", self.entry["config"], ".json", dirs))
        self.model = load_module(find("configs", self.entry["config"], ".py", dirs))
        self.traffic = load_json(find("traffic", self.entry["traffic"], ".json", dirs))
        self.limits = load_json(find("cells", name, ".json", dirs))
        self.e2e = cell_metrics(bench, name, "end_to_end")
        self.per_layer = cell_metrics(bench, name, "per_layer")
        self.dirs = dirs

    def reader(self, metric: str):
        return load_module(find("metrics", metric, ".py", self.dirs)).read


# ---- the comparison that decides ``correct`` -------------------------------

def _leaves(tree, prefix=""):
    """{path: flat float64 array} of a nested dict of arrays."""
    out = {}
    for k in sorted(tree):
        v = tree[k]
        if isinstance(v, dict):
            out.update(_leaves(v, f"{prefix}{k}/"))
        else:
            out[prefix + k] = np.asarray(v, np.float64).ravel()
    return out


def param_gap(got, want) -> float:
    """The worst leaf's ||got - want|| over the reference's norm of that
    leaf or of the median leaf, whichever is larger."""
    g, w = _leaves(got), _leaves(want)
    if sorted(g) != sorted(w):
        return float("inf")
    norms = {k: float(np.linalg.norm(v)) for k, v in w.items()}
    floor = float(np.median(list(norms.values())))
    gaps = []
    for k in w:
        if g[k].shape != w[k].shape or not np.all(np.isfinite(g[k])):
            return float("inf")
        gaps.append(np.linalg.norm(g[k] - w[k]) / max(norms[k], floor))
    return float(max(gaps))


def accuracy_gap(got: dict, want: dict) -> float:
    """The widest gap, in test rows, between the program's accuracy after
    each round (its history, and the final ``accuracy`` against the
    reference's last) and the reference's."""
    hist, ref = got["accuracy"], want["accuracy"]
    if len(hist) != len(ref) or not ref:
        return float("inf")
    gaps = [abs(a - b) for a, b in zip(hist + [got["final_accuracy"]],
                                       ref + [ref[-1]])]
    return float(max(gaps) * want["test_rows"])


EXACT = ("rounds", "stop_reason", "members")


def compare(got: Sequence[dict], want: Sequence[dict], limits: dict):
    """``got`` / ``want``: per compared session, ``params``, ``rounds``,
    ``stop_reason``, ``members`` and the ``accuracy`` after each round.
    Returns (correct, failed sessions, checks) with checks
    {name: {"value", "limit"}}."""
    gaps = [param_gap(g["params"], w["params"]) for g, w in zip(got, want)]
    acc = [accuracy_gap(g, w) for g, w in zip(got, want)]
    off = {key: sum(g[key] != w[key] for g, w in zip(got, want))
           for key in EXACT}
    checks = {"param_gap": {"value": max(gaps), "limit": limits["param_gap"]["limit"]},
              "acc_gap_rows": {"value": max(acc),
                               "limit": limits["acc_gap_rows"]["limit"]},
              "rounds_off": {"value": off["rounds"], "limit": 0},
              "stop_off": {"value": off["stop_reason"], "limit": 0},
              "members_off": {"value": off["members"], "limit": 0}}
    failed = sum(1 for k in range(len(got))
                 if gaps[k] > checks["param_gap"]["limit"]
                 or acc[k] > checks["acc_gap_rows"]["limit"]
                 or any(got[k][key] != want[k][key] for key in EXACT))
    correct = (len(got) == len(want) > 0
               and all(c["value"] <= c["limit"] for c in checks.values()))
    return correct, failed, checks


def program_sessions(result, sessions: Sequence[int]) -> List[dict]:
    import jax
    picked = [result.sessions[i] for i in sessions]
    params = jax.device_get([s.params for s in picked])
    return [dict(params=p, rounds=int(s.rounds), stop_reason=s.stop_reason,
                 members=int(s.n_contributors),
                 accuracy=[float(a) for a in s.history_raw["accuracy"]],
                 final_accuracy=float(s.accuracy))
            for p, s in zip(params, picked)]


def compared_sessions(traffic: dict, seed: int) -> List[int]:
    """A sample, drawn from the seed, of the requesters to compare."""
    r = traffic["requesters"]
    k = min(r, traffic["compared_sessions"])
    return sorted(np.random.default_rng(seed).choice(r, size=k, replace=False).tolist())


# ---- the run ---------------------------------------------------------------

def check_chips(chips: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise NoChip(f"needs {chips} TPU chip(s); JAX found "
                     f"{len(devices)} {devices[0].platform} device(s)")
    return devices


def enable_compile_cache() -> str:
    """The program's persistent compilation cache
    (``repro.utils.compile_cache``: ``JAX_COMPILATION_CACHE_DIR`` where it
    is set, else ``<checkout>/.jax_cache``), with every program cached,
    however small or quick to compile, so that only a cell's first run in
    a checkout compiles."""
    import jax
    from repro.utils import compile_cache
    path = compile_cache.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def build(cell: Cell, seed: int):
    """The program's world and method for ``cell`` from ``seed``, and the
    plain world the reference reads."""
    import world as world_mod
    ws = world_mod.world_seed(seed)
    data = cell.model.dataset(cell.conf, ws)
    plain = world_mod.draw_world(cell.conf, cell.traffic, data, seed)
    task = cell.model.program_task(cell.conf)
    spec = world_mod.program_world(task, plain, cell.conf, cell.traffic)
    return spec, world_mod.method_spec(cell.traffic), plain


def run_study(spec, method):
    from repro.api import ExecutionSpec, Experiment
    return Experiment(spec, method, ExecutionSpec(engine="fleet")).run()


def study_row(result, t0: float, t1: float, host: dict) -> dict:
    spans = result.timings
    return {"t0": t0, "t1": t1, "wall_s": result.wall_s, "host": host,
            **{k: spans.get(k, 0.0) for k in HOST_SPANS}}


class HostMeter:
    """What the host did during one study: the process's CPU seconds (all
    threads) and the seconds Python's garbage collector ran.  A slow
    study shows by these, and by its spans, whether it worked longer,
    collected, or waited."""

    FIELDS = ("cpu_s", "gc_s")

    def __init__(self):
        self.gc_s, self._gc_t0 = 0.0, None
        gc.callbacks.append(self._gc)

    def _gc(self, phase, info):
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0 is not None:
            self.gc_s += time.perf_counter() - self._gc_t0

    def read(self) -> tuple:
        return time.process_time(), self.gc_s

    @staticmethod
    def delta(a: tuple, b: tuple) -> dict:
        return {k: y - x for k, x, y in zip(HostMeter.FIELDS, a, b)}

    def close(self):
        gc.callbacks.remove(self._gc)


def describe(row: dict) -> str:
    h, wall = row["host"], row["t1"] - row["t0"]
    spans = ", ".join(f"{k} {row[k]:.4f}" for k in HOST_SPANS)
    return (f"{wall:.4f} s ({spans}, rest "
            f"{wall - sum(row[k] for k in HOST_SPANS):.4f}; cpu "
            f"{h['cpu_s']:.2f} s, gc {h['gc_s']:.4f} s)")


def traced_study(spec, method):
    """One study under the profiler, marked by one TraceAnnotation.
    Returns the reduced trace."""
    import jax
    import devtrace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(ANNOTATION):
                entry = time.perf_counter()
                result = run_study(spec, method)
        finally:
            jax.profiler.stop_trace()
        profile = jax.profiler.ProfileData.from_file(devtrace.xplane_file(d))
        w0, _ = devtrace.annotation_window(profile, ANNOTATION)
        tl = result.timeline
        to_ns = lambda t: w0 + (tl._epoch + t - entry) * 1e9
        spans = [(s.name, to_ns(s.t0), to_ns(s.t0 + s.dur))
                 for s in tl.spans if s.name in HOST_SPANS]
        return devtrace.reduce(profile, ANNOTATION, spans)


def breakdown(reduced: dict) -> dict:
    """The ten device operations with the most self time, and the ten
    longest idle gaps by the host span they fell in."""
    ops = sorted(((f"{k} {v['shape']}"[:120], v["seconds"])
                  for k, v in reduced["ops"].items()), key=lambda x: -x[1])[:10]
    gaps = sorted(reduced["gaps"], key=lambda x: -x[1])[:10]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in gaps]}


def run_cell(bench: dict, name: str, seed: int, seconds: float, trace: bool,
             t_start: float, dirs: Sequence[Path] = (HERE,),
             require_chips: bool = True, log=None):
    """Set-up, window, optional traced study and the comparison; returns
    (result line as a dict, checks)."""
    import jax
    import jax.numpy as jnp

    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    cell = Cell(bench, name, dirs)
    if require_chips:
        devices = check_chips(cell.entry["chips"])
        enable_compile_cache()
    else:
        devices = jax.devices()
    import peaks
    import reference

    device = devices[0]
    t_imported = time.perf_counter()
    spec, method, plain = build(cell, seed)
    t_built = time.perf_counter()
    run_study(spec, method)                      # compiles or loads, warms up
    gc.collect()                                 # the warm-up's garbage
    window_start = time.perf_counter()
    setup_s = window_start - t_start
    log(f"set-up {setup_s:.2f} s: imports {t_imported - t_start:.2f} s, "
        f"world {t_built - t_imported:.2f} s, warm-up study "
        f"{window_start - t_built:.2f} s")

    studies, last, meter = [], None, HostMeter()
    while not studies or studies[-1]["t1"] - window_start < seconds:
        last = None                              # hold one study's result
        h0, t0 = meter.read(), time.perf_counter()
        last = run_study(spec, method)
        t1 = time.perf_counter()
        studies.append(study_row(last, t0, t1, meter.delta(h0, meter.read())))
    meter.close()
    stats = device.memory_stats() or {}
    peak_bytes = stats.get("peak_bytes_in_use")
    walls = [s["t1"] - s["t0"] for s in studies]
    by_wall = sorted(studies, key=lambda s: s["t1"] - s["t0"])
    log(f"window {studies[-1]['t1'] - window_start:.2f} s, {len(studies)} "
        f"studies of {min(walls):.4f} to {max(walls):.4f} s "
        f"(first {walls[0]:.4f} s, median {statistics.median(walls):.4f} s)")
    log(f"slowest study {describe(by_wall[-1])}; median study "
        f"{describe(by_wall[len(by_wall) // 2])}")

    reduced = traced_study(spec, method) if trace else None

    compared = compared_sessions(cell.traffic, seed)
    got = program_sessions(last, compared)
    executed = int(sum(s.rounds for s in last.sessions))
    del last, spec
    gc.collect()
    want = reference.study(cell.model.Reference(cell.conf), plain, cell.conf,
                           cell.traffic, compared, jnp.float32)
    correct, failed, checks = compare(got, want, cell.limits)

    record = {
        "requesters": cell.traffic["requesters"],
        "studies": studies, "setup_s": setup_s,
        "elapsed_s": studies[-1]["t1"] - window_start,
        "rounds_executed": executed, "conf": cell.conf,
        "traffic": cell.traffic, "model": cell.model,
        "params": param_count(cell), "peak": peaks.peak(device.device_kind)
        if require_chips else None, "device_trace": reduced}
    entries = cell.per_layer if trace else cell.e2e
    metrics = {}
    for m in entries:
        value = cell.reader(m["name"])(record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": len(devices), "memory_peak_bytes": peak_bytes}
    if reduced is not None:
        dev.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
    line = {"correct": bool(correct),
            "attempted": len(studies) * cell.traffic["requesters"],
            "failed": failed, "metrics": metrics, "device": dev}
    if reduced is not None:
        line["breakdown"] = breakdown(reduced)
    line["checks"] = checks
    return line, checks


def param_count(cell: Cell) -> int:
    import jax
    shapes = jax.eval_shape(cell.model.Reference(cell.conf).init,
                            jax.random.PRNGKey(0))
    return int(sum(np.prod(s.shape) for s in jax.tree_util.tree_leaves(shapes)))
