"""The harness on the CPU, at a size a test run holds: cells, metrics and
configurations are found by name, a run drives the program and the
reference, and the comparison fails where the timed path is broken.

``run_cell(..., require_chips=False)`` skips only the look for a chip;
the rest of a run is the one the chip runs.  The test-only cell
``mlp.tiny`` is a traffic file and a cell file in a directory of the
test's own, which the harness finds beside the committed ones."""

import json
import subprocess
import sys
import time

import numpy as np
import pytest

import bench
import faults

TINY = {"requesters": 3, "contributors": 5, "own_samples": 128,
        "compared_sessions": 3,
        "method": {"max_rounds": 2, "epochs": 2, "batch_size": 32,
                   "n_max": 5, "contributor_refresh_epochs": 1,
                   "desired_accuracy": 1.01, "offered_incentive": 0.6}}


@pytest.fixture
def tiny(tmp_path):
    """BENCHMARK.json plus a test-only cell, and the directories to find
    its files in: the test's own first, then the committed ones."""
    (tmp_path / "traffic").mkdir()
    (tmp_path / "cells").mkdir()
    (tmp_path / "traffic" / "tiny.json").write_text(json.dumps(TINY))
    limits = bench.load_json(bench.HERE / "cells" / "mlp.paper-1.json")
    (tmp_path / "cells" / "mlp.tiny.json").write_text(json.dumps(limits))
    spec = bench.load_json(bench.ROOT / "BENCHMARK.json")
    spec["workloads"].append({"name": "mlp.tiny", "config": "paper-mlp",
                              "traffic": "tiny", "chips": 1, "why": "test"})
    return spec, (tmp_path, bench.HERE)


def run_tiny(tiny, trace=False):
    spec, dirs = tiny
    return bench.run_cell(spec, "mlp.tiny", 2**31 + 7, 0.2, trace,
                          time.perf_counter(), dirs=dirs,
                          require_chips=False, log=lambda m: None)


def test_benchmark_files_exist_for_every_name():
    spec = bench.load_json(bench.ROOT / "BENCHMARK.json")
    for w in spec["workloads"]:
        cell = bench.Cell(spec, w["name"])
        for m in cell.e2e + cell.per_layer:
            assert callable(cell.reader(m["name"]))
    for c in spec["configs"]:
        assert (bench.ROOT / c["file"]).is_file()
        assert bench.load_json(bench.ROOT / c["file"])["reduced"] == c["reduced"]


def test_configs_are_the_papers_models():
    from repro.configs import PAPER_LSTM, PAPER_MLP
    mlp = bench.load_json(bench.HERE / "configs" / "paper-mlp.json")["model"]
    lstm = bench.load_json(bench.HERE / "configs" / "paper-lstm.json")["model"]
    assert (mlp["input_dim"], tuple(mlp["hidden"]), mlp["num_classes"]) == (
        PAPER_MLP.input_dim, PAPER_MLP.hidden, PAPER_MLP.num_classes)
    assert (lstm["input_dim"], lstm["seq_len"], lstm["hidden"],
            lstm["num_classes"]) == (PAPER_LSTM.input_dim, PAPER_LSTM.seq_len,
                                     PAPER_LSTM.hidden, PAPER_LSTM.num_classes)


def test_every_seed_gives_the_same_shapes():
    import world
    spec = bench.load_json(bench.ROOT / "BENCHMARK.json")
    for name in ("mlp.population-256", "lstm.paper-16"):
        cell = bench.Cell(spec, name)
        shapes = set()
        for seed in (0, 5, 2**31 + 11, 2**33 + 3):
            data = cell.model.dataset(cell.conf, world.world_seed(seed))
            w = world.draw_world(cell.conf, cell.traffic, data, seed)
            shapes.add((tuple(s[0].shape for s in w.shards),
                        tuple(o[0].shape for o in w.own_train),
                        w.own_test[0].shape))
        assert len(shapes) == 1


def test_test_only_cell_runs_and_is_correct(tiny):
    line, checks = run_tiny(tiny)
    assert line["correct"] is True
    assert line["attempted"] >= TINY["requesters"]
    assert set(line["metrics"]) == {"sessions_per_s", "setup_s"}
    assert list(line)[-1] == "checks"
    assert checks["param_gap"]["value"] <= checks["param_gap"]["limit"]


@pytest.mark.parametrize("fault", faults.FAULTS)
def test_broken_timed_path_is_not_correct(tiny, fault):
    with faults.planted(fault):
        line, checks = run_tiny(tiny)
    assert line["correct"] is False, checks


def test_method_knobs_reach_the_program_and_bound_the_reference():
    """Every ``method`` knob of a traffic file reaches the program's
    ``MethodSpec`` as it is, and the reference refuses a knob it does
    not model."""
    import reference
    import world
    traffic = dict(TINY, method=dict(TINY["method"], compress="int8"))
    spec = world.method_spec(traffic)
    assert spec.compress == "int8" and spec.max_rounds == 2
    with pytest.raises(ValueError, match="models exactly"):
        reference.study(None, None, {}, traffic, [0])


def test_bf16_control_is_not_correct(tiny):
    """The reference computed in bfloat16, in the program's place, fails
    the comparison at the committed limit."""
    import jax.numpy as jnp
    import reference
    spec, dirs = tiny
    cell = bench.Cell(spec, "mlp.tiny", dirs)
    _, _, plain = bench.build(cell, 3)
    sessions = bench.compared_sessions(cell.traffic, 3)
    ref = cell.model.Reference(cell.conf)
    want = reference.study(ref, plain, cell.conf, cell.traffic, sessions)
    ctl = reference.study(ref, plain, cell.conf, cell.traffic, sessions,
                          jnp.bfloat16)
    correct, failed, checks = bench.compare(ctl, want, cell.limits)
    assert not correct and failed == len(sessions), checks


def test_param_gap_reads_the_worst_leaf():
    want = {"a": {"w": np.ones((4, 4)), "b": np.zeros(4)}, "c": np.ones(3)}
    got = {"a": {"w": np.ones((4, 4)), "b": np.full(4, 0.1)}, "c": np.ones(3)}
    # b's reference norm is 0, so the median leaf's norm (sqrt 3) scales it
    assert bench.param_gap(got, want) == pytest.approx(0.2 / np.sqrt(3))
    assert bench.param_gap(want, want) == 0.0
    got["c"] = np.array([1.0, np.nan, 1.0])
    assert bench.param_gap(got, want) == float("inf")


def test_accuracy_gap_reads_the_widest_round_in_rows():
    want = {"accuracy": [0.5, 0.75], "final_accuracy": 0.75, "test_rows": 128}
    got = {"accuracy": [0.5, 0.75], "final_accuracy": 0.75}
    assert bench.accuracy_gap(got, want) == 0.0
    got["accuracy"] = [0.5 + 3 / 128, 0.75]
    assert bench.accuracy_gap(got, want) == pytest.approx(3.0)
    # the reported final accuracy is held to the reference's last round
    got = {"accuracy": [0.5, 0.75], "final_accuracy": 0.75 - 2 / 128}
    assert bench.accuracy_gap(got, want) == pytest.approx(2.0)
    got = {"accuracy": [0.5], "final_accuracy": 0.5}
    assert bench.accuracy_gap(got, want) == float("inf")


def test_no_chip_exits_2_with_no_result():
    proc = subprocess.run(
        [sys.executable, str(bench.HERE / "run.py"), "--workload",
         "mlp.paper-1", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, cwd=bench.ROOT, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert proc.returncode == 2, proc.stderr
    assert proc.stdout == ""
