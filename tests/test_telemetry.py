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
import glob
import json
import os

import numpy as np
import pytest
from jax.flatten_util import ravel_pytree

from repro.api import ExecutionSpec, Experiment, MethodSpec, WorldSpec
from repro.core import (FaultConfig, MobilityConfig, RequesterSpec,
                        SupervisedTask, make_fleet)
from repro.data import (CaloriesDatasetConfig, dirichlet_partition,
                        make_calories_tabular)
from repro.models import MLPClassifier, MLPClassifierConfig
from repro.telemetry import (EVENT_PHASES, RoundEvent, Timeline, TraceConfig,
                             compare_event_streams, hlo_phases,
                             read_events_jsonl, timeline_chrome_trace,
                             validate_events, write_chrome_trace,
                             write_events_jsonl)

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
                        jax_profiler_dir=str(tmp_path / "profile"),
                        hlo_stats=(engine == "fleet"))
    off = Experiment(_world(problem, mobility), method,
                     ExecutionSpec(engine=engine)).run()
    on = Experiment(_world(problem, mobility), method,
                    ExecutionSpec(engine=engine, trace=trace)).run()
    _assert_outcome_bitwise(off, on)
    # and the traced run actually observed something
    assert (tmp_path / "events.jsonl").exists()
    assert (tmp_path / "trace.json").exists()
    assert _xplane(tmp_path / "profile")
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
# the spans of one fleet study, on the profiler's clock
# ---------------------------------------------------------------------------

STUDY_SPANS = ["copy_world", "stage", "program", "unpack", "views", "assemble"]
STAGE_SPANS = ["handshake", "shards", "stack", "arrays", "refresh_dedup",
               "init_state"]
UNPACK_SPANS = ["fetch", "writeback", "unravel"]


def _shared_world(problem, n_req=2):
    """``n_req`` requesters over one contributor population: every lane
    of a device stages the same shard and the same params, so the staged
    shards and refresh rows are the devices, not the lanes."""
    task, own_train, own_test, fleet, states = problem
    return WorldSpec(task=task, requesters=[
        RequesterSpec(own_train, own_test, fleet, copy.deepcopy(states))
        for _ in range(n_req)])


def _children(tl, idx):
    return [s for s in tl.spans if s.parent == idx]


def _xplane(log_dir):
    return glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                  "*.xplane.pb"))


def test_fleet_study_span_tree(problem):
    """One study's spans, in order, with the counts their parents' work
    produced — equal to what the FleetResult reports."""
    res = Experiment(_shared_world(problem), _METHOD,
                     ExecutionSpec(engine="fleet")).run()
    tl, fr = res.timeline, res.raw
    top = [i for i, s in enumerate(tl.spans) if s.parent is None]
    assert [tl.spans[i].name for i in top] == STUDY_SPANS
    by_name = {tl.spans[i].name: i for i in top}
    stage = _children(tl, by_name["stage"])
    unpack = _children(tl, by_name["unpack"])
    assert [s.name for s in stage] == STAGE_SPANS
    assert [s.name for s in unpack] == UNPACK_SPANS
    attrs = {s.name: s.attrs for s in tl.spans}
    n_devices = len(problem[4])
    lanes = sum(s.n_contributors for s in fr.sessions)
    assert lanes == 2 * n_devices
    assert attrs["shards"] == {"lanes": lanes, "shards": n_devices}
    assert attrs["refresh_dedup"] == {"live_rows": n_devices}
    assert attrs["arrays"] == {"bytes": fr.staged_host_bytes}
    # one device->host copy of the final (R, N, P) fp32 contributor
    # state, N = the n_max contract slots, signed or not
    n_params = ravel_pytree(next(iter(problem[4].values()))["params"])[0].size
    assert attrs["writeback"] == {
        "views": lanes,
        "bytes": len(fr.sessions) * _METHOD.n_max * n_params * 4}
    assert attrs["views"] == {"sessions": len(fr.sessions)}
    # children lie inside their parents, the study inside its wall time
    for s in tl.spans:
        if s.parent is not None:
            p = tl.spans[s.parent]
            assert p.t0 <= s.t0 and s.t0 + s.dur <= p.t0 + p.dur
    assert sum(tl.spans[i].dur for i in top) <= res.wall_s


def test_study_spans_are_host_events_of_a_profiler_trace(problem, tmp_path):
    """Under a jax.profiler trace each Timeline span is a host event of
    the same name, nested in the study's annotation and in its parent's
    event, in the Timeline's order."""
    import jax
    exp = Experiment(_shared_world(problem), _METHOD,
                     ExecutionSpec(engine="fleet"))
    exp.run()                                      # compile outside
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("study"):
            res = exp.run()
    [path] = _xplane(tmp_path)
    profile = jax.profiler.ProfileData.from_file(path)
    names = {s.name for s in res.timeline.spans} | {"study"}
    [events] = [ev for plane in profile.planes
                if plane.name.startswith("/host:") for line in plane.lines
                for ev in [[(e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events if e.name in names]] if ev]
    events.sort(key=lambda e: (e[1], -e[2]))
    (study, s0, s1), spans = events[0], events[1:]
    assert study == "study"
    assert [e[0] for e in spans] == [s.name for s in res.timeline.spans]
    for (name, a, b), sp in zip(spans, res.timeline.spans):
        pa, pb = (s0, s1) if sp.parent is None else spans[sp.parent][1:]
        assert pa <= a and b <= pb, name


@pytest.mark.parametrize("engine", ["loop", "fleet"])
def test_profiler_knob_wraps_the_whole_run(problem, engine, tmp_path,
                                           recwarn):
    """``TraceConfig.jax_profiler_dir`` profiles the whole
    ``Experiment.run`` on either engine, world copy to result, and the
    loop engine takes it without a warning."""
    import jax
    res = Experiment(_world(problem), _METHOD, ExecutionSpec(
        engine=engine, trace=TraceConfig(jax_profiler_dir=str(tmp_path))
    )).run()
    assert not [w for w in recwarn if "TraceConfig" in str(w.message)]
    [path] = _xplane(tmp_path)
    profile = jax.profiler.ProfileData.from_file(path)
    seen = [e.name for plane in profile.planes
            if plane.name.startswith("/host:") for line in plane.lines
            for e in line.events]
    top = [s.name for s in res.timeline.spans if s.parent is None]
    assert top[0] == "copy_world" and top[-1] == "assemble"
    assert set(top) <= set(seen)


def test_fleet_program_names_its_protocol_phases(problem):
    """Every protocol phase the static enfed round runs is a
    ``jax.named_scope``: the compiled program's instructions map to
    them (``hlo_stats["phases"]``)."""
    res = Experiment(_world(problem), _METHOD, ExecutionSpec(
        engine="fleet", trace=TraceConfig(hlo_stats=True))).run()
    phases = res.hlo_stats["phases"]
    assert {"fit", "score", "aggregate", "refresh", "account",
            "other"} <= set(phases.values())
    assert not {"renegotiate", "deliver"} & set(phases.values())


def test_hlo_phases_reads_scopes_and_fusion_roots():
    hlo = """HloModule jit_f

%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %add.1 = f32[8]{0} add(%param_0, %param_0), metadata={op_name="jit(f)/while/body/transpose(jvp(fit))/add"}
}

%fused_computation.2 (param_0.2: f32[8]) -> (f32[8], f32[8]) {
  %param_0.2 = f32[8]{0} parameter(0)
  %mul.2 = f32[8]{0} multiply(%param_0.2, %param_0.2), metadata={op_name="jit(f)/refresh/vmap(fit_refresh)/mul"}
  ROOT %tuple.2 = (f32[8]{0}, f32[8]{0}) tuple(%mul.2, %param_0.2)
}

ENTRY %main.9 (x.1: f32[8]) -> f32[8] {
  %x.1 = f32[8]{0} parameter(0)
  %add_fusion = f32[8]{0} fusion(%x.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/other_scope/add"}
  %multi_fusion = (f32[8]{0}, f32[8]{0}) fusion(%add_fusion), kind=kLoop, calls=%fused_computation.2
  %get-tuple-element.3 = f32[8]{0} get-tuple-element(%multi_fusion), index=0
  ROOT %reduce.4 = f32[8]{0} negate(%get-tuple-element.3), metadata={op_name="jit(f)/score/epoch_scores/neg"}
}
"""
    phases = hlo_phases(hlo)
    assert phases["add_fusion"] == "fit"        # its root's scope
    assert phases["multi_fusion"] == "refresh"  # the tuple root's operand
    assert phases["reduce.4"] == "score"        # not "epoch_scores"
    assert phases["x.1"] == phases["get-tuple-element.3"] == "other"


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
