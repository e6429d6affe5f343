"""repro.telemetry: the observability layer and its house rule.

The one invariant everything here enforces: OBSERVATION CAN NEVER CHANGE
THE SIMULATED OUTCOME.  A run with tracing on (event JSONL, Chrome
trace, HLO stats) must be bitwise identical — params, masks, battery —
to the same run with tracing off, on static, mobility, and fault worlds,
through both engines.  On top of that: the two engines' normalized event
streams on one world must be equal, the exporters must round-trip
schema-valid, and the Timeline span stack must behave.
"""

import copy
import dataclasses
import json

import numpy as np
import pytest
from jax.flatten_util import ravel_pytree

from repro.api import ExecutionSpec, Experiment, MethodSpec, WorldSpec
from repro.core import (FaultConfig, MobilityConfig, SupervisedTask,
                        make_fleet)
from repro.data import (CaloriesDatasetConfig, dirichlet_partition,
                        make_calories_tabular)
from repro.models import MLPClassifier, MLPClassifierConfig
from repro.telemetry import (EVENT_PHASES, RoundEvent, Timeline, TraceConfig,
                             compare_event_streams, read_events_jsonl,
                             timeline_chrome_trace, validate_events,
                             write_chrome_trace, write_events_jsonl)

from test_cadence import CC_SLOW_REQ

BATCH = 16


def _build(n_contrib=3, n_samples=600, seed=0):
    x, y = make_calories_tabular(CaloriesDatasetConfig(num_samples=n_samples))
    task = SupervisedTask(MLPClassifier(MLPClassifierConfig(8, (16,), 5)), lr=3e-3)
    parts = dirichlet_partition(y, num_clients=n_contrib + 1, alpha=100.0, seed=seed)
    shards = [(x[p], y[p]) for p in parts]
    own_x, own_y = shards[0]
    n = int(len(own_x) * 0.8)
    own_train, own_test = (own_x[:n], own_y[:n]), (own_x[n:], own_y[n:])
    fleet = make_fleet(n_contrib, seed=1, p_has_model=1.0)
    states = {}
    for i, dev in enumerate(fleet):
        dev.reservation_price = 0.4
        p = task.init(seed=10 + i)
        p, _ = task.fit(p, shards[i + 1], epochs=1, batch_size=BATCH, seed=i)
        states[dev.device_id] = {"params": p, "data": shards[i + 1]}
    return task, own_train, own_test, fleet, states


@pytest.fixture(scope="module")
def problem():
    return _build()


_METHOD = MethodSpec(desired_accuracy=0.99, max_rounds=2, epochs=1,
                     batch_size=BATCH, encrypt=False,
                     contributor_refresh_epochs=1)
_MOB = MobilityConfig(radio_range_m=95.0, leg_rounds=1, seed=5)
_FAULTS = FaultConfig(p_drop=0.6, p_stale=0.4, max_retries=1,
                      release_after=2, seed=3)
# the requester on stride 2 of 2 — real idle steps between rounds, so
# the async observability fields carry non-trivial values
_CADENCE = CC_SLOW_REQ

# world name -> (mobility, method) — the weather regimes the house rule
# is enforced on (cadence = the async event-step world of PR 9)
_WORLDS = {
    "static": (None, _METHOD),
    "mobility": (_MOB, dataclasses.replace(_METHOD, desired_accuracy=0.999,
                                           max_rounds=4, n_max=2)),
    "faults": (None, dataclasses.replace(_METHOD, desired_accuracy=0.999,
                                         max_rounds=4, faults=_FAULTS)),
    "cadence": (None, dataclasses.replace(_METHOD, desired_accuracy=0.999,
                                          max_rounds=3, cadence=_CADENCE)),
}


def _world(problem, mobility=None):
    task, own_train, own_test, fleet, states = problem
    return WorldSpec.single(task, own_train, own_test, fleet,
                            copy.deepcopy(states), mobility=mobility)


def _assert_outcome_bitwise(a, b):
    """Two RunResults computed the identical simulation: params, every
    history buffer (masks, battery, counters), rounds, stop reason."""
    assert a.rounds == b.rounds
    assert a.stop_reason == b.stop_reason
    av, _ = ravel_pytree(a.params)
    bv, _ = ravel_pytree(b.params)
    np.testing.assert_array_equal(np.asarray(av), np.asarray(bv))
    assert set(a.history_raw) == set(b.history_raw)
    for k in a.history_raw:
        ha, hb = a.history_raw[k], b.history_raw[k]
        assert len(ha) == len(hb), f"history[{k!r}] length"
        # row-wise: mobility histories hold per-round mask rows whose
        # width varies with the candidate pool
        for r, (ra, rb) in enumerate(zip(ha, hb)):
            np.testing.assert_array_equal(np.asarray(ra), np.asarray(rb),
                                          err_msg=f"history[{k!r}][{r}]")


# ---------------------------------------------------------------------------
# the house rule: tracing on == tracing off, bit for bit
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("world_name", list(_WORLDS))
@pytest.mark.parametrize("engine", ["loop", "fleet"])
def test_trace_on_is_bitwise_identical_to_trace_off(problem, engine,
                                                    world_name, tmp_path):
    mobility, method = _WORLDS[world_name]
    # exercise the heaviest trace on the fleet engine (profiling hooks
    # included); the loop engine gets the exports that apply to it
    trace = TraceConfig(events_jsonl=str(tmp_path / "events.jsonl"),
                        chrome_trace=str(tmp_path / "trace.json"),
                        hlo_stats=(engine == "fleet"))
    off = Experiment(_world(problem, mobility), method,
                     ExecutionSpec(engine=engine)).run()
    on = Experiment(_world(problem, mobility), method,
                    ExecutionSpec(engine=engine, trace=trace)).run()
    _assert_outcome_bitwise(off, on)
    # and the traced run actually observed something
    assert (tmp_path / "events.jsonl").exists()
    assert (tmp_path / "trace.json").exists()
    assert on.timings
    if engine == "fleet":
        assert on.hlo_stats and "flops" in on.hlo_stats


# ---------------------------------------------------------------------------
# cross-engine event-stream equality
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("world_name", list(_WORLDS))
def test_event_streams_equal_across_engines(problem, world_name):
    mobility, method = _WORLDS[world_name]
    loop = Experiment(_world(problem, mobility), method,
                      ExecutionSpec(engine="loop")).run()
    fl = Experiment(_world(problem, mobility), method,
                    ExecutionSpec(engine="fleet")).run()
    diffs = compare_event_streams(validate_events(loop.trace),
                                  validate_events(fl.trace))
    assert diffs == []


def test_fault_world_events_carry_the_weather(problem):
    """The fault world's drops/retries/stale and delivered sets must
    surface in the normalized stream, not just in raw history."""
    _, method = _WORLDS["faults"]
    res = Experiment(_world(problem), method,
                     ExecutionSpec(engine="fleet")).run()
    rounds = [e for e in res.trace if e.phase == "round"]
    assert sum(e.drops for e in rounds) > 0
    assert sum(e.retries for e in rounds) > 0
    assert all(e.delivered is not None for e in rounds)
    # wire bytes follow the delivered count, priced per session
    mb = res.sessions[0].model_bytes
    assert mb > 0
    assert all(e.wire_bytes == mb * len(e.delivered) for e in rounds)
    stops = [e for e in res.trace if e.phase == "stop"]
    assert len(stops) == 1 and stops[0].stop_reason == res.stop_reason


@pytest.mark.parametrize("engine", ["loop", "fleet"])
def test_cadence_world_events_carry_lane_clocks(problem, engine):
    """Async-cadence observability rides the ONE adapter: the per-event
    clock/idle fields are mapped from the engines' round_clock/idle_steps
    history buffers, never emitted from engine code — and lockstep worlds
    leave them None (absence, not zero)."""
    _, method = _WORLDS["cadence"]
    res = Experiment(_world(problem), method,
                     ExecutionSpec(engine=engine)).run()
    rounds = [e for e in res.trace if e.phase == "round"]
    clock_h = res.sessions[0].history_raw["round_clock"]
    idle_h = res.sessions[0].history_raw["idle_steps"]
    assert [e.clock for e in rounds] == [int(c) for c in clock_h]
    assert [e.idle for e in rounds] == [float(i) for i in idle_h]
    assert all(isinstance(e.clock, int) for e in rounds)
    assert all(isinstance(e.idle, float) for e in rounds)
    # requester stride 2 of 2: clocks advance on the global event
    # counter, strictly faster than the round index, with real idle gaps
    assert all(b > a for a, b in zip([e.clock for e in rounds],
                                     [e.clock for e in rounds][1:]))
    assert rounds[-1].clock > rounds[-1].round
    assert sum(e.idle for e in rounds) > 0
    stop = [e for e in res.trace if e.phase == "stop"]
    assert len(stop) == 1 and stop[0].clock is None and stop[0].idle is None
    # lockstep world: no cadence concept, so the fields stay None
    lock = Experiment(_world(problem), _METHOD,
                      ExecutionSpec(engine=engine)).run()
    assert all(e.clock is None and e.idle is None for e in lock.trace)


# ---------------------------------------------------------------------------
# exporters: JSONL round-trip, schema validation, Chrome trace
# ---------------------------------------------------------------------------


def test_events_jsonl_round_trips(problem, tmp_path):
    res = Experiment(_world(problem), _METHOD,
                     ExecutionSpec(engine="loop")).run()
    path = str(tmp_path / "events.jsonl")
    n = write_events_jsonl(res.trace, path)
    back = read_events_jsonl(path)
    assert n == len(back) == len(res.trace)
    assert back == res.trace          # frozen dataclasses: field equality
    # machine-readable: every line is one standalone JSON object
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    assert len(rows) == n
    assert all(row["phase"] in EVENT_PHASES for row in rows)


def _event(**over):
    base = dict(round=0, requester=0, phase="round", executed=True,
                members=None, member_set=None, delivered=None,
                drops=0.0, retries=0.0, stale=0.0, battery=None,
                accuracy=0.5, loss=None, wire_bytes=0, energy_j=None,
                stop_reason=None)
    base.update(over)
    return RoundEvent(**base)


def test_validate_events_rejects_schema_violations():
    ok = [_event(), _event(round=1),
          _event(round=2, phase="stop", stop_reason="accuracy_reached")]
    assert validate_events(ok) == ok
    with pytest.raises(ValueError, match="phase"):
        validate_events([_event(phase="negotiate")])
    with pytest.raises(ValueError, match="stop_reason"):
        validate_events([_event(phase="stop")])          # stop w/o reason
    with pytest.raises(ValueError, match="stop_reason"):
        validate_events([_event(stop_reason="oops")])    # reason on round
    with pytest.raises(ValueError, match="does not follow"):
        validate_events([_event(), _event(round=2)])     # round gap
    with pytest.raises(ValueError, match="already stopped"):
        validate_events([_event(phase="stop", stop_reason="x"),
                         _event(round=1)])
    with pytest.raises(ValueError, match="bool"):
        validate_events([_event(wire_bytes=True)])       # bool is not int
    with pytest.raises(ValueError, match="accuracy"):
        validate_events([_event(accuracy=None)])         # non-noneable


def test_compare_event_streams_reports_diffs():
    a = [_event(accuracy=0.5)]
    assert compare_event_streams(a, [_event(accuracy=0.5 + 1e-6)]) == []
    assert compare_event_streams(a, [_event(accuracy=0.9)])
    assert compare_event_streams(a, [_event(drops=1.0)])
    assert compare_event_streams(a, a + [_event(round=1)])


def test_chrome_trace_structure(tmp_path):
    tl = Timeline()
    with tl.span("stage", what="x"):
        with tl.span("quantize_pack"):
            pass
    with tl.span("program", cache_miss=True):
        pass
    doc = timeline_chrome_trace(tl)
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    evs = doc["traceEvents"]
    assert [e["name"] for e in evs] == ["stage", "quantize_pack", "program"]
    for e in evs:
        assert e["ph"] == "X" and e["ts"] >= 0 and e["dur"] >= 0
        assert e["cat"] == "repro"
    assert evs[0]["args"] == {"what": "x"}
    # nested span lies inside its parent on the µs timeline
    assert evs[1]["ts"] >= evs[0]["ts"]
    assert evs[1]["ts"] + evs[1]["dur"] <= evs[0]["ts"] + evs[0]["dur"]
    path = str(tmp_path / "trace.json")
    assert write_chrome_trace(tl, path) == 3
    with open(path) as f:
        assert json.load(f) == doc


# ---------------------------------------------------------------------------
# Timeline spans
# ---------------------------------------------------------------------------


def test_timeline_nesting_and_totals():
    tl = Timeline()
    with tl.span("outer"):
        with tl.span("inner"):
            pass
        with tl.span("inner"):
            pass
    outer, i1, i2 = tl.spans
    assert (outer.depth, i1.depth, i2.depth) == (0, 1, 1)
    assert i1.parent == 0 and i2.parent == 0
    totals = tl.totals()
    # nested spans total under their own name, inside the parent's wall
    assert totals["inner"] <= totals["outer"]
    assert tl.total("inner") == totals["inner"]
    assert tl.total("missing") == 0.0


def test_timeline_finish_is_strictly_lifo():
    tl = Timeline()
    a = tl.begin("a")
    tl.begin("b")
    with pytest.raises(RuntimeError, match="innermost"):
        tl.finish(a)


def test_open_span_excluded_from_totals_and_trace():
    tl = Timeline()
    tl.begin("open")
    assert tl.totals() == {}
    assert timeline_chrome_trace(tl)["traceEvents"] == []


# ---------------------------------------------------------------------------
# the ExecutionSpec knob
# ---------------------------------------------------------------------------


def test_execution_spec_rejects_non_trace_config():
    with pytest.raises(ValueError, match="TraceConfig"):
        ExecutionSpec(trace={"events_jsonl": "x.jsonl"})


def test_loop_engine_warns_on_fleet_only_trace_knobs(problem, tmp_path):
    trace = TraceConfig(hlo_stats=True)
    with pytest.warns(UserWarning, match="hlo_stats"):
        Experiment(_world(problem), _METHOD,
                   ExecutionSpec(engine="loop", trace=trace)).run()
