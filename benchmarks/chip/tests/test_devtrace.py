"""The trace reduction on a trace recorded on a TPU v5e: one warm study
of ``mlp.paper-1`` (R = 1, N = 5, 10 rounds) under the profiler, inside
the benchmark's TraceAnnotation."""

import gzip
from pathlib import Path

import pytest

import bench
import devtrace
import peaks

TRACE = Path(__file__).parent / "data" / "mlp.paper-1.xplane.pb.gz"


@pytest.fixture(scope="module")
def reduced():
    import jax
    profile = jax.profiler.ProfileData.from_serialized_xspace(
        gzip.decompress(TRACE.read_bytes()))
    w0, w1 = devtrace.annotation_window(profile, bench.ANNOTATION)
    # a host span over the first half of the study, to label gaps by
    spans = [("stage", w0, (w0 + w1) / 2)]
    return devtrace.reduce(profile, bench.ANNOTATION, spans)


def test_busy_and_gaps_fill_the_window(reduced):
    assert reduced["devices"] == 1
    assert 0 < reduced["busy_s"] < reduced["window_s"]
    idle = sum(s for _, s in reduced["gaps"])
    assert idle + reduced["busy_s"] == pytest.approx(reduced["window_s"], rel=1e-9)
    # self times of nested operations add up to the busy time
    self_s = sum(op["seconds"] for op in reduced["ops"].values())
    assert self_s == pytest.approx(reduced["busy_s"], rel=1e-9)
    assert {label for label, _ in reduced["gaps"]} == {"stage", "outside"}


def test_fedavg_kernel_and_its_pad_are_found(reduced):
    [kernel] = [op for op in reduced["ops"].values()
                if op["instr"].startswith("fedavg_batched_pallas")]
    assert kernel["module"] == "jit__fleet_program"
    assert kernel["opcode"] == "custom-call" and kernel["count"] == 10
    feeds = [reduced["ops"].get(f"jit__fleet_program/{o}")
             for o in kernel["operands"]]
    assert [f["opcode"] for f in feeds if f is not None] == ["pad"]
    seconds, launches = devtrace.kernel_time(reduced, "fedavg_batched_pallas")
    assert launches == 10 and seconds > kernel["seconds"]


def test_metrics_of_the_trace_are_shares(reduced):
    spec = bench.load_json(bench.ROOT / "BENCHMARK.json")
    cell = bench.Cell(spec, "mlp.paper-1")
    rec = {"device_trace": reduced, "peak": peaks.peak("TPU v5 lite"),
           "requesters": 1, "traffic": cell.traffic,
           "params": bench.param_count(cell)}
    roofline = cell.reader("fedavg_roofline")(rec)
    idle = cell.reader("device_idle")(rec)
    assert 0 < roofline <= 100 and 0 < idle < 100
    assert cell.reader("fedavg_roofline")(dict(rec, device_trace=None)) is None
    b = bench.breakdown(reduced)
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 10
    assert all(isinstance(n, str) and s > 0 for n, s in b["device_ops"])


def test_parse_op():
    op = devtrace.parse_op(
        "%fedavg_batched_pallas.2 = f32[1,4096]{1,0:T(1,128)S(1)} custom-call("
        "f32[1,5]{1,0:T(1,128)S(1)} %get-tuple-element.904, f32[1,5,4096]"
        "{2,1,0:T(8,128)S(1)} %pad.3), custom_call_target=\"tpu_custom_call\"")
    assert op == {"instr": "fedavg_batched_pallas.2", "opcode": "custom-call",
                  "shape": "f32[1,4096]",
                  "operands": ["get-tuple-element.904", "pad.3"]}
    op = devtrace.parse_op(
        "%while.44 = (s32[]{:T(128)}, f32[1,64]{1,0:T(1,128)S(1)}) "
        "while((s32[]{:T(128)}, f32[1,64]{1,0:T(1,128)}) %tuple.5), "
        "condition=%c, body=%b")
    assert op["opcode"] == "while" and op["operands"] == ["tuple.5"]


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks.peak("TPU v9 imaginary")
