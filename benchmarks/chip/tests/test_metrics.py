"""The metric readers on a hand-made record."""

import pytest

import bench
import peaks


@pytest.fixture(scope="module")
def cell():
    return bench.Cell(bench.load_json(bench.ROOT / "BENCHMARK.json"),
                      "mlp.population-256")


def record(cell, walls):
    studies, t = [], 0.0
    for w in walls:
        studies.append({"t0": t, "t1": t + w, "wall_s": w - 0.001,
                        "stage": 0.2 * w, "program": 0.1 * w, "unpack": 0.5 * w})
        t += w
    return {"requesters": 256, "studies": studies, "elapsed_s": t,
            "setup_s": 12.5, "rounds_executed": 256 * 3, "conf": cell.conf,
            "traffic": cell.traffic, "model": cell.model,
            "params": bench.param_count(cell), "peak": peaks.peak("TPU v5 lite"),
            "device_trace": None}


def test_host_clock_and_span_metrics(cell):
    rec = record(cell, [2.0] * 9 + [4.0])
    read = lambda m: cell.reader(m)(rec)
    assert read("sessions_per_s") == pytest.approx(2560 / 22.0)
    assert read("setup_s") == 12.5
    assert read("stage_ms") == pytest.approx(1e3 * 0.2 * 22 / 10)
    assert read("program_ms") == pytest.approx(1e3 * 0.1 * 22 / 10)
    assert read("unpack_ms") == pytest.approx(1e3 * 0.5 * 22 / 10)
    assert read("facade_ms") == pytest.approx(1e3 * (0.2 * 22 - 0.01) / 10)
    assert read("study_p90_ms") == pytest.approx(3800.0)   # 2 s x 9, 4 s x 1
    assert cell.reader("study_p90_ms")(record(cell, [1.0] * 9)) is None
    assert read("device_idle") is None and read("fedavg_roofline") is None


def test_study_mfu_counts_the_model_flops(cell):
    rec = record(cell, [10.0, 10.0])
    mfu = bench.load_module(bench.find("metrics", "study_mfu", ".py"))
    fwd = 2 * (8 * 64 + 64 * 32 + 32 * 5)          # 8-64-32-5 MLP
    assert cell.model.forward_flops(cell.conf) == fwd
    fits = 256 * 3 * 5 * 128 * 3 * fwd              # R x rounds x E x rows
    refresh = 5 * 3 * 1 * 640 * 3 * fwd              # N x rounds x 1 epoch
    evals = 256 * 3 * 128 * fwd                     # R x rounds x test rows
    assert mfu.study_flops(rec) == fits + refresh + evals
    assert cell.reader("study_mfu")(rec) == pytest.approx(
        100 * 2 * (fits + refresh + evals) / (20.0 * 197e12))
    assert 0 < cell.reader("study_mfu")(rec) < 100


def test_lstm_forward_flops():
    spec = bench.load_json(bench.ROOT / "BENCHMARK.json")
    lstm = bench.Cell(spec, "lstm.paper-16")
    assert lstm.model.forward_flops(lstm.conf) == 64 * 2 * (6 + 64) * 256 + 2 * 64 * 6
    assert bench.param_count(lstm) == 18566
    mlp = bench.Cell(spec, "mlp.paper-1")
    assert bench.param_count(mlp) == 2821
