"""The program's spans and phases in a trace, the per-phase device
metrics and the span report: on a trace recorded on a TPU v5e (one warm
``mlp.paper-1`` study, R = 1, N = 5, 10 rounds, by ``layers.py
--keep-trace``, with the phase map of the instructions it ran), the
readers on hand-made records, the phase map of a real compile of the
fleet program on the CPU, and the span tree of a window of studies."""

import gzip
import json
from pathlib import Path

import pytest

import bench
import devtrace
import layers
import spantrace
from test_harness import TINY

DATA = Path(__file__).parent / "data"
TRACE = DATA / "mlp.paper-1.spans.xplane.pb.gz"
PHASES = DATA / "mlp.paper-1.spans.phases.json"
STUDY = ["copy_world", "stage", "stage/handshake", "stage/shards",
         "stage/stack", "stage/arrays", "stage/refresh_dedup",
         "stage/init_state", "program", "unpack", "unpack/fetch",
         "unpack/writeback", "unpack/unravel", "views", "assemble"]

PHASE_METRICS = ("fit_device_ms", "score_device_ms", "refresh_device_ms",
                 "aggregate_device_ms")


@pytest.fixture(scope="module")
def spec():
    return bench.load_json(bench.ROOT / "BENCHMARK.json")


@pytest.fixture(scope="module")
def tiny_rec(spec):
    """A record of the paper MLP at the harness test's tiny traffic."""
    cell = bench.Cell(spec, "mlp.paper-1")
    return {"model": cell.model, "conf": cell.conf, "traffic": TINY}


@pytest.fixture(scope="module")
def recorded():
    """The recorded study: its host spans by path and the reduced trace
    with its gaps labelled by them."""
    import jax
    profile = jax.profiler.ProfileData.from_serialized_xspace(
        gzip.decompress(TRACE.read_bytes()))
    names = {p.split("/")[-1] for p in STUDY}
    spans = spantrace.host_spans(profile, bench.ANNOTATION, names)
    return spans, devtrace.reduce(profile, bench.ANNOTATION, spans)


def test_host_spans_cut_the_study_by_innermost_span(recorded):
    spans, _ = recorded
    assert list(dict.fromkeys(p for p, _, _ in spans)) == STUDY
    # segments in time order, none overlapping, every child inside its
    # parent's extent
    assert all(b0 <= a1 for (_, _, b0), (_, a1, _) in zip(spans, spans[1:]))
    assert all(a < b for _, a, b in spans)
    extent = {}
    for p, a, b in spans:
        lo, hi = extent.get(p, (a, b))
        extent[p] = (min(lo, a), max(hi, b))
    for path, (a, b) in extent.items():
        if "/" in path:
            pa, pb = extent[path.split("/")[0]]
            assert pa <= a and b <= pb, path


def test_gaps_are_labelled_by_span_path(recorded):
    _, reduced = recorded
    labels = [label for label, _ in reduced["gaps"]]
    assert set(labels) <= set(STUDY) | {"outside"}
    assert {"stage/arrays", "stage/init_state", "unpack/writeback",
            "views"} <= set(labels)
    # an idle millisecond outside every span lies before the first span
    # or after the last one, never between them
    inner = [s for label, s in reduced["gaps"][1:-1] if label == "outside"]
    assert not [s for s in inner if s >= 1e-3]


def test_phase_times_fill_the_programs_device_time(recorded):
    _, reduced = recorded
    phases = json.loads(PHASES.read_text())
    seconds = spantrace.phase_time(reduced, phases)
    assert spantrace.UNMAPPED not in seconds
    program = sum(seconds.values())
    assert program <= reduced["busy_s"]
    assert program == pytest.approx(sum(
        op["seconds"] for op in reduced["ops"].values()
        if op["module"] == spantrace.PROGRAM), rel=1e-12)
    assert seconds["other"] < 0.1 * program
    assert min(seconds[p] for p in ("fit", "score", "refresh",
                                    "aggregate")) > 0
    kernel = [i for i, p in phases.items()
              if i.startswith("fedavg_batched_pallas")]
    assert kernel and all(phases[i] == "aggregate" for i in kernel)


def traced_rec(ops, phases):
    """A record whose traced study ran ``ops``: (module, instr, seconds),
    with the phase map already made."""
    return {"device_trace": {"ops": {
        f"{m}/{i}": {"module": m, "instr": i, "seconds": s}
        for m, i, s in ops}}, "phase_map": phases}


def test_phase_metrics_read_the_programs_self_time(spec):
    cell = bench.Cell(spec, "mlp.population-256")
    p = spantrace.PROGRAM
    rec = traced_rec([(p, "fusion.1", 0.002), (p, "while.3", 0.001),
                      (p, "fusion.2", 0.004), (p, "fedavg_batched_pallas.1",
                                               0.0005),
                      (p, "copy.9", 0.0001), (p, "sort.8", 0.003),
                      ("jit_dynamic_slice", "fusion.1", 0.5)],
                     {"fusion.1": "fit", "while.3": "fit",
                      "fusion.2": "refresh", "sort.8": "refresh",
                      "fedavg_batched_pallas.1": "aggregate",
                      "copy.9": "other"})
    got = {m: cell.reader(m)(rec) for m in PHASE_METRICS}
    assert got == pytest.approx({"fit_device_ms": 3.0, "score_device_ms": 0.0,
                                 "refresh_device_ms": 7.0,
                                 "aggregate_device_ms": 0.5})
    times = spantrace.phase_time(rec["device_trace"], rec["phase_map"])
    assert sum(times.values()) == pytest.approx(0.0106)
    assert all(cell.reader(m)(dict(rec, device_trace=None)) is None
               for m in PHASE_METRICS)


def test_a_map_of_another_compile_is_an_error():
    p = spantrace.PROGRAM
    rec = traced_rec([(p, "fusion.1", 0.002), (p, "fusion.77", 0.001)],
                     {"fusion.1": "fit"})
    with pytest.raises(RuntimeError, match="phase map"):
        spantrace.phase_ms(rec, "fit")


def test_a_program_without_phase_scopes_reads_nothing(monkeypatch, tiny_rec):
    """Where the program names no phases, as before its named scopes,
    the readers return None and compile nothing."""
    from repro.telemetry import profile
    monkeypatch.delattr(profile, "hlo_phases")
    rec = dict(tiny_rec, device_trace={"ops": {}})
    assert spantrace.phase_map(rec) is None
    assert spantrace.phase_ms(rec, "fit") is None


def test_phase_map_of_the_cells_program(tiny_rec):
    rec = dict(tiny_rec)
    phases = spantrace.phase_map(rec)
    assert {"fit", "score", "aggregate", "refresh", "account",
            "other"} <= set(phases.values())
    assert spantrace.phase_map(rec) is phases          # once per record


def test_span_report_of_a_window(spec):
    cell = bench.Cell(spec, "mlp.paper-1")
    cell.traffic = TINY
    world, method, _ = bench.build(cell, 5)
    bench.run_study(world, method)
    rows, elapsed = layers.window(world, method, 0.0)
    assert len(rows) == 1 and elapsed > 0
    report = layers.span_report(rows + rows)
    assert list(report["span_ms"])[:3] == ["copy_world", "stage",
                                           "stage/handshake"]
    assert 0.9 < report["top_cover"] <= 1.0
    assert 0.9 < report["stage_cover"] <= 1.0
    assert 0.9 < report["unpack_cover"] <= 1.0
    assert report["unpack_largest"] in ("unpack/fetch", "unpack/writeback",
                                        "unpack/unravel")
    assert report["attrs"]["views"] == {"sessions": TINY["requesters"]}
    assert report["attrs"]["stage/shards"] == {
        "lanes": TINY["requesters"] * TINY["contributors"],
        "shards": TINY["contributors"]}
    assert layers.span_cost_us(1000) > 0
