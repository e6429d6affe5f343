"""Loop-engine / fleet-engine parity (Algorithm 1, two executions).

The loop engine (`repro.core.rounds.EnFedSession`) is the readable
reference oracle; the fleet engine (`repro.core.fleet.run_fleet`)
compiles many concurrent requester sessions into one jit program.  These
tests assert the fleet engine reproduces the oracle exactly: aggregated
params (allclose), round counts, stop reasons, and per-round battery
trajectories — across aggregation strategies, encrypt on/off, and all
three stop conditions.
"""

import copy
import dataclasses

import jax
import numpy as np
import pytest
from jax.flatten_util import ravel_pytree

from repro.core import (AggregationStrategy, EnFedConfig, EnFedSession,
                        MobilityConfig, RequesterSpec, SupervisedTask,
                        make_fleet, run_fleet)
from repro.core.battery import BatteryState
from repro.data import CaloriesDatasetConfig, dirichlet_partition, make_calories_tabular
from repro.models import MLPClassifier, MLPClassifierConfig

BATCH = 16


def _build(n_contrib=3, n_samples=600, seed=0):
    """One tiny HAR-style problem: shared task, requester shard + test
    split, a contributor fleet with pre-trained states."""
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


def _run_both(problem, cfg, battery_kw=None):
    """Run the same session through both engines on fresh copies of the
    mutable state (contributor params, battery)."""
    task, own_train, own_test, fleet, states = problem
    battery_kw = battery_kw or {}
    loop = EnFedSession(task, own_train, own_test, fleet, copy.deepcopy(states),
                        cfg, battery=BatteryState(**battery_kw)).run()
    spec = RequesterSpec(own_train=own_train, own_test=own_test, neighborhood=fleet,
                         contributor_states=copy.deepcopy(states),
                         battery=BatteryState(**battery_kw))
    fleet_res = run_fleet(task, [spec], cfg)
    return loop, fleet_res.sessions[0]


def _assert_parity(loop, fl):
    assert fl.rounds == loop.rounds
    assert fl.stop_reason == loop.stop_reason
    assert fl.n_contributors == loop.n_contributors
    np.testing.assert_allclose(fl.history_raw["accuracy"], loop.history_raw["accuracy"],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(fl.history_raw["battery"], loop.history_raw["battery"],
                               rtol=1e-5, atol=1e-6)
    lv, _ = ravel_pytree(loop.params)
    fv, _ = ravel_pytree(fl.params)
    np.testing.assert_allclose(np.asarray(fv), np.asarray(lv), rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# parity across strategies x encryption
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("strategy,encrypt", [
    (None, True),                                            # paper default
    (AggregationStrategy(kind="dfl_mesh"), False),           # full mesh
    (AggregationStrategy(kind="dfl_ring"), False),           # ring neighbours
    (AggregationStrategy(kind="cfl"), True),                 # virtual server
    (AggregationStrategy(kind="enfed", neighborhood_size=2), True),
], ids=["default-enc", "mesh-plain", "ring-plain", "cfl-enc", "enfed2-enc"])
def test_fleet_matches_loop_across_strategies(problem, strategy, encrypt):
    cfg = EnFedConfig(desired_accuracy=0.99, max_rounds=2, epochs=2,
                      batch_size=BATCH, encrypt=encrypt,
                      contributor_refresh_epochs=1, strategy=strategy)
    loop, fl = _run_both(problem, cfg)
    assert loop.stop_reason == "max_rounds"
    _assert_parity(loop, fl)


# ---------------------------------------------------------------------------
# stop conditions
# ---------------------------------------------------------------------------


def test_fleet_stops_on_accuracy_like_loop(problem):
    cfg = EnFedConfig(desired_accuracy=0.05, max_rounds=4, epochs=1,
                      batch_size=BATCH, encrypt=False,
                      contributor_refresh_epochs=0)
    loop, fl = _run_both(problem, cfg)
    assert loop.stop_reason == "accuracy_reached" and loop.rounds == 1
    _assert_parity(loop, fl)


def test_fleet_stops_on_battery_like_loop(problem):
    # tiny battery: one round's energy drains it below the threshold
    cfg = EnFedConfig(desired_accuracy=0.99, max_rounds=4, epochs=1,
                      batch_size=BATCH, encrypt=False,
                      contributor_refresh_epochs=0)
    loop, fl = _run_both(problem, cfg, battery_kw=dict(capacity_j=0.2, level=0.3))
    assert loop.stop_reason == "battery_low"
    _assert_parity(loop, fl)


def test_fleet_writes_back_refreshed_contributors(problem):
    """Side-effect parity: after a session with contributor refresh, both
    engines leave the SAME refreshed params in contributor_states."""
    task, own_train, own_test, fleet, states = problem
    cfg = EnFedConfig(desired_accuracy=0.99, max_rounds=2, epochs=1,
                      batch_size=BATCH, encrypt=False,
                      contributor_refresh_epochs=1)
    loop_states = copy.deepcopy(states)
    EnFedSession(task, own_train, own_test, fleet, loop_states, cfg).run()
    fleet_states = copy.deepcopy(states)
    run_fleet(task, [RequesterSpec(own_train, own_test, fleet, fleet_states)], cfg)
    for dev_id, st in loop_states.items():
        before, _ = ravel_pytree(states[dev_id]["params"])
        lv, _ = ravel_pytree(st["params"])
        fv, _ = ravel_pytree(fleet_states[dev_id]["params"])
        assert not np.allclose(np.asarray(lv), np.asarray(before)), "refresh ran"
        np.testing.assert_allclose(np.asarray(fv), np.asarray(lv),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("compress,tol", [
    (None, dict(rtol=1e-4, atol=1e-5)),
    ("int8", dict(atol=1e-2)),          # the wire image, as test_compress
], ids=["fp32", "int8"])
def test_fleet_multi_requester_writeback_is_read_only_host_views(
        problem, compress, tol):
    """R = 3 requesters, each with its own shard and its own shallow copy
    of the states dict (as ``WorldSpec.fresh_requesters`` makes): each
    dict ends up holding that requester's loop-engine refresh, as
    read-only numpy leaves; one dict shared by all three holds the last
    requester's lanes."""
    task, own_train, own_test, fleet, states = problem
    cfg = EnFedConfig(desired_accuracy=0.99, max_rounds=3, epochs=1,
                      batch_size=BATCH, encrypt=False,
                      contributor_refresh_epochs=1, compress=compress)
    shards = [(own_train[0][i::3], own_train[1][i::3]) for i in range(3)]
    # the last requester's battery stops it after one round, so its
    # contributors' final params differ from the other two's
    battery_kw = [{}, {}, dict(capacity_j=0.2, level=0.3)]

    def specs(dicts):
        return [RequesterSpec(sh, own_test, fleet, st, BatteryState(**kw))
                for sh, st, kw in zip(shards, dicts, battery_kw)]

    own = [{k: dict(v) for k, v in states.items()} for _ in shards]
    run_fleet(task, specs(own), cfg)
    for sh, st, kw in zip(shards, own, battery_kw):
        loop_states = copy.deepcopy(states)
        EnFedSession(task, sh, own_test, fleet, loop_states, cfg,
                     battery=BatteryState(**kw)).run()
        for dev_id in states:
            leaves = jax.tree_util.tree_leaves(st[dev_id]["params"])
            assert all(isinstance(l, np.ndarray) and not l.flags.writeable
                       for l in leaves)
            with pytest.raises(ValueError):
                leaves[0][...] = 0.0
            lv, _ = ravel_pytree(loop_states[dev_id]["params"])
            fv, _ = ravel_pytree(st[dev_id]["params"])
            np.testing.assert_allclose(np.asarray(fv), np.asarray(lv), **tol)
    first, last = ([ravel_pytree(o[d]["params"])[0] for d in states]
                   for o in (own[0], own[-1]))
    assert not all(np.array_equal(a, b) for a, b in zip(first, last))
    shared = {k: dict(v) for k, v in states.items()}
    run_fleet(task, specs([shared] * len(shards)), cfg)
    for dev_id in states:
        jax.tree_util.tree_map(np.testing.assert_array_equal,
                               shared[dev_id]["params"],
                               own[-1][dev_id]["params"])


def test_session_fleet_engine_flag(problem):
    """EnFedSession.run(engine='fleet') routes through the fleet engine."""
    task, own_train, own_test, fleet, states = problem
    cfg = EnFedConfig(desired_accuracy=0.99, max_rounds=1, epochs=1,
                      batch_size=BATCH, encrypt=False,
                      contributor_refresh_epochs=0)
    sess = EnFedSession(task, own_train, own_test, fleet, copy.deepcopy(states), cfg)
    res = sess.run(engine="fleet")
    ref = EnFedSession(task, own_train, own_test, fleet, copy.deepcopy(states), cfg).run()
    assert res.rounds == ref.rounds and res.stop_reason == ref.stop_reason
    np.testing.assert_allclose(res.accuracy, ref.accuracy, rtol=1e-5)
    assert sess.battery.level == pytest.approx(ref.battery.level, rel=1e-5)
    with pytest.raises(ValueError):
        sess.run(engine="warp")


# ---------------------------------------------------------------------------
# many concurrent sessions in one program
# ---------------------------------------------------------------------------


def test_fleet_runs_64_concurrent_sessions(problem):
    """>= 64 requester sessions advance in ONE jit program, and lanes
    match per-session loop-engine runs spot-checked at both ends."""
    task, own_train, own_test, fleet, states = problem
    cfg = EnFedConfig(desired_accuracy=0.99, max_rounds=1, epochs=1,
                      batch_size=BATCH, encrypt=False,
                      contributor_refresh_epochs=0)
    R = 64
    rng = np.random.default_rng(0)
    specs = []
    for i in range(R):
        # distinct shards per requester: rotate + subsample the own shard
        sel = rng.permutation(len(own_train[0]))[:max(BATCH * 2, len(own_train[0]) // 2)]
        specs.append(RequesterSpec(
            own_train=(own_train[0][sel], own_train[1][sel]),
            own_test=own_test, neighborhood=fleet,
            contributor_states=copy.deepcopy(states), battery=BatteryState()))
    result = run_fleet(task, specs, cfg)
    assert len(result.sessions) == R
    assert result.rounds.shape == (R,) and (result.rounds == 1).all()
    assert result.history_raw["accuracy"].shape == (cfg.max_rounds, R)
    assert result.total_energy_j > 0

    for lane in (0, R - 1):
        loop = EnFedSession(task, (specs[lane].own_train[0], specs[lane].own_train[1]),
                            own_test, fleet, copy.deepcopy(states), cfg).run()
        fl = result.sessions[lane]
        assert fl.rounds == loop.rounds and fl.stop_reason == loop.stop_reason
        np.testing.assert_allclose(fl.history_raw["accuracy"], loop.history_raw["accuracy"],
                                   rtol=1e-5, atol=1e-6)
        lv, _ = ravel_pytree(loop.params)
        fv, _ = ravel_pytree(fl.params)
        np.testing.assert_allclose(np.asarray(fv), np.asarray(lv),
                                   rtol=1e-4, atol=1e-5)


def test_fleet_rejects_empty():
    with pytest.raises(ValueError):
        run_fleet(None, [])


def test_shard_staging_dedups_equal_content(problem):
    """Contributor shards are staged ONCE per unique (device, content)
    pair even when every RequesterSpec deep-copies the states dict (the
    standard usage pattern) — object identity must not defeat the dedup."""
    task, own_train, own_test, fleet, states = problem
    cfg = EnFedConfig(desired_accuracy=0.99, max_rounds=1, epochs=1,
                      batch_size=BATCH, encrypt=False,
                      contributor_refresh_epochs=1)
    R = 4
    specs = [RequesterSpec(own_train, own_test, fleet, copy.deepcopy(states))
             for _ in range(R)]
    res = run_fleet(task, specs, cfg)
    assert res.staged_shard_bytes_dense > 0
    # R requesters sharing one 3-device population: ~R x fewer bytes
    assert res.staged_shard_bytes < res.staged_shard_bytes_dense / (R - 1)


def test_fleet_sub_batch_shard_matches_loop():
    """A requester shard smaller than one batch runs in the fleet engine
    as a single padded+masked step — and matches the loop engine, which
    takes the same padded step through the shared derived schedule."""
    task, own_train, own_test, fleet, states = _build(n_contrib=2, n_samples=300)
    tiny = (own_train[0][:BATCH - 4], own_train[1][:BATCH - 4])  # < one batch
    cfg = EnFedConfig(desired_accuracy=0.99, max_rounds=2, epochs=2,
                      batch_size=BATCH, encrypt=False,
                      contributor_refresh_epochs=1)
    loop = EnFedSession(task, tiny, own_test, fleet, copy.deepcopy(states),
                        cfg).run()
    fl = run_fleet(task, [RequesterSpec(tiny, own_test, fleet,
                                        copy.deepcopy(states))], cfg).sessions[0]
    _assert_parity(loop, fl)


def test_fleet_mixed_sub_batch_and_full_lanes():
    """Sub-batch and full-batch requesters coexist in ONE program; each
    lane matches its own loop-engine run."""
    task, own_train, own_test, fleet, states = _build(n_contrib=2, n_samples=300)
    shards = [(own_train[0][:BATCH // 2], own_train[1][:BATCH // 2]),
              (own_train[0], own_train[1])]
    cfg = EnFedConfig(desired_accuracy=0.99, max_rounds=2, epochs=1,
                      batch_size=BATCH, encrypt=False,
                      contributor_refresh_epochs=0)
    specs = [RequesterSpec(sh, own_test, fleet, copy.deepcopy(states))
             for sh in shards]
    result = run_fleet(task, specs, cfg)
    for lane, sh in enumerate(shards):
        loop = EnFedSession(task, sh, own_test, fleet,
                            copy.deepcopy(states), cfg).run()
        _assert_parity(loop, result.sessions[lane])


# ---------------------------------------------------------------------------
# churn: the opportunistic world (repro.core.mobility) in both engines
# ---------------------------------------------------------------------------


def _assert_churn_parity(loop, fl):
    """Static parity PLUS the mobility surface: per-round membership
    masks and member counts must be bit-identical."""
    _assert_parity(loop, fl)
    np.testing.assert_array_equal(np.array(loop.history_raw["member_mask"]),
                                  np.array(fl.history_raw["member_mask"]))
    assert loop.history_raw["members"] == fl.history_raw["members"]


@pytest.mark.parametrize("mob_kw,cfg_kw", [
    # devices wander in/out of a 110 m radio range every 2 rounds
    (dict(radio_range_m=110.0, leg_rounds=2, seed=3), {}),
    # sparse world: rounds with an EMPTY neighborhood (requester trains alone)
    (dict(radio_range_m=55.0, leg_rounds=2, seed=3), {}),
    # encrypted transport while churning
    (dict(radio_range_m=110.0, leg_rounds=2, seed=3), dict(encrypt=True)),
    # battery-floor releases drive the churn (static positions, tiny
    # contributor batteries): members drain out and are replaced
    (dict(mode="static", radio_range_m=500.0, seed=3,
          contributor_capacity_j=0.004, battery_floor=0.3), {}),
], ids=["waypoint-churn", "empty-rounds", "churn-encrypted", "floor-release"])
def test_fleet_matches_loop_under_mobility(problem, mob_kw, cfg_kw):
    cfg_base = dict(desired_accuracy=0.99, max_rounds=6, epochs=1,
                    batch_size=BATCH, encrypt=False, n_max=2,
                    contributor_refresh_epochs=1,
                    mobility=MobilityConfig(**mob_kw))
    cfg_base.update(cfg_kw)
    loop, fl = _run_both(problem, EnFedConfig(**cfg_base))
    _assert_churn_parity(loop, fl)


def test_mobility_renegotiation_actually_churns(problem):
    """The parity gate must exercise RE-NEGOTIATION, not a static mask:
    this config provably changes membership mid-session in both engines."""
    cfg = EnFedConfig(desired_accuracy=0.99, max_rounds=6, epochs=1,
                      batch_size=BATCH, encrypt=False, n_max=2,
                      contributor_refresh_epochs=1,
                      mobility=MobilityConfig(radio_range_m=55.0,
                                              leg_rounds=2, seed=3))
    loop, fl = _run_both(problem, cfg)
    _assert_churn_parity(loop, fl)
    masks = np.array(loop.history_raw["member_mask"])
    assert (masks != masks[0]).any(), "membership must change mid-session"


def test_mobility_strategies_follow_dynamic_members(problem):
    """Aggregation strategies compose with churn: the enfed/ring round
    weights are derived from the CURRENT membership each round."""
    for strategy in (AggregationStrategy(kind="enfed", neighborhood_size=2),
                     AggregationStrategy(kind="dfl_ring")):
        cfg = EnFedConfig(desired_accuracy=0.99, max_rounds=4, epochs=1,
                          batch_size=BATCH, encrypt=False, n_max=3,
                          contributor_refresh_epochs=1, strategy=strategy,
                          mobility=MobilityConfig(radio_range_m=130.0,
                                                  leg_rounds=2, seed=7))
        loop, fl = _run_both(problem, cfg)
        _assert_churn_parity(loop, fl)


def test_mobility_battery_stop_parity(problem):
    cfg = EnFedConfig(desired_accuracy=0.99, max_rounds=6, epochs=1,
                      batch_size=BATCH, encrypt=False, n_max=3,
                      contributor_refresh_epochs=1,
                      mobility=MobilityConfig(radio_range_m=110.0,
                                              leg_rounds=2, seed=3))
    loop, fl = _run_both(problem, cfg, battery_kw=dict(capacity_j=0.2, level=0.3))
    assert loop.stop_reason == "battery_low"
    _assert_churn_parity(loop, fl)


def test_mobility_multi_lane_fleet_matches_per_lane_loops(problem):
    """Concurrent churning sessions in ONE program: fleet lane i walks as
    requester_id + i, so each lane must match a loop run configured with
    that requester id."""
    task, own_train, own_test, fleet, states = problem
    mob = MobilityConfig(radio_range_m=110.0, leg_rounds=2, seed=3)
    cfg = EnFedConfig(desired_accuracy=0.99, max_rounds=4, epochs=1,
                      batch_size=BATCH, encrypt=False, n_max=2,
                      contributor_refresh_epochs=1, mobility=mob)
    R = 3
    specs = [RequesterSpec(own_train, own_test, fleet, copy.deepcopy(states))
             for _ in range(R)]
    result = run_fleet(task, specs, cfg)
    saw_different_worlds = False
    ref_members = result.sessions[0].history_raw["members"]
    for lane in range(R):
        lane_cfg = dataclasses.replace(
            cfg, mobility=dataclasses.replace(
                mob, requester_id=mob.requester_id + lane))
        loop = EnFedSession(task, own_train, own_test, fleet,
                            copy.deepcopy(states), lane_cfg).run()
        _assert_churn_parity(loop, result.sessions[lane])
        if result.sessions[lane].history_raw["members"] != ref_members:
            saw_different_worlds = True
    assert saw_different_worlds, "lanes should see distinct neighborhoods"


def test_mobility_writes_back_member_refreshed_contributors(problem):
    """Refresh write-back under churn: only devices that were members
    while the session ran get trained; both engines leave identical
    contributor params behind."""
    task, own_train, own_test, fleet, states = problem
    cfg = EnFedConfig(desired_accuracy=0.99, max_rounds=4, epochs=1,
                      batch_size=BATCH, encrypt=False, n_max=2,
                      contributor_refresh_epochs=1,
                      mobility=MobilityConfig(radio_range_m=110.0,
                                              leg_rounds=2, seed=3))
    loop_states = copy.deepcopy(states)
    EnFedSession(task, own_train, own_test, fleet, loop_states, cfg).run()
    fleet_states = copy.deepcopy(states)
    run_fleet(task, [RequesterSpec(own_train, own_test, fleet, fleet_states)], cfg)
    for dev_id in states:
        lv, _ = ravel_pytree(loop_states[dev_id]["params"])
        fv, _ = ravel_pytree(fleet_states[dev_id]["params"])
        np.testing.assert_allclose(np.asarray(fv), np.asarray(lv),
                                   rtol=1e-4, atol=1e-5)


def test_session_fleet_engine_flag_with_mobility(problem):
    """EnFedSession.run(engine='fleet') carries cfg.mobility through."""
    task, own_train, own_test, fleet, states = problem
    cfg = EnFedConfig(desired_accuracy=0.99, max_rounds=3, epochs=1,
                      batch_size=BATCH, encrypt=False, n_max=2,
                      contributor_refresh_epochs=0,
                      mobility=MobilityConfig(radio_range_m=110.0,
                                              leg_rounds=2, seed=3))
    res = EnFedSession(task, own_train, own_test, fleet,
                       copy.deepcopy(states), cfg).run(engine="fleet")
    ref = EnFedSession(task, own_train, own_test, fleet,
                       copy.deepcopy(states), cfg).run()
    _assert_churn_parity(ref, res)


# ---------------------------------------------------------------------------
# early exit: a converged fleet executes O(k), not O(max_rounds), bodies
# ---------------------------------------------------------------------------


def _baseline_client_data(own_train, fleet, states):
    """The roster both engines train for a baseline method: the
    requester's shard first, then each neighborhood device's shard."""
    return [own_train] + [states[dev.device_id]["data"] for dev in fleet]


def test_fleet_baseline_cfl_matches_loop(problem):
    """method="cfl" lanes of the fleet program reproduce the CFLLearner
    oracle: same accuracy trajectory, rounds, stop, aggregated params."""
    from repro.core.federated import CFLLearner

    task, own_train, own_test, fleet, states = problem
    cfg = EnFedConfig(desired_accuracy=0.99, max_rounds=3, epochs=2,
                      batch_size=BATCH, seed=5)
    loop = CFLLearner(task, _baseline_client_data(own_train, fleet, states),
                      own_test).run_config(cfg)
    fl = run_fleet(task, [RequesterSpec(own_train, own_test, fleet,
                                        copy.deepcopy(states))],
                   cfg, method="cfl").sessions[0]
    assert fl.rounds == loop.rounds
    assert fl.battery is None
    assert fl.stop_reason == ("accuracy_reached"
                              if loop.accuracy >= cfg.desired_accuracy
                              else "max_rounds")
    np.testing.assert_allclose(fl.history_raw["accuracy"], loop.history_raw["accuracy"],
                               rtol=1e-5, atol=1e-6)
    lv, _ = ravel_pytree(loop.params)
    fv, _ = ravel_pytree(fl.params)
    np.testing.assert_allclose(np.asarray(fv), np.asarray(lv),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("topology", ["mesh", "ring"])
def test_fleet_baseline_dfl_matches_loop(problem, topology):
    """method="dfl" lanes (mesh AND ring gossip) reproduce DFLLearner."""
    from repro.core.federated import DFLLearner

    task, own_train, own_test, fleet, states = problem
    cfg = EnFedConfig(desired_accuracy=0.99, max_rounds=3, epochs=1,
                      batch_size=BATCH, seed=5)
    loop = DFLLearner(task, _baseline_client_data(own_train, fleet, states),
                      own_test, topology).run_config(cfg)
    fl = run_fleet(task, [RequesterSpec(own_train, own_test, fleet,
                                        copy.deepcopy(states))],
                   cfg, method="dfl", dfl_topology=topology).sessions[0]
    assert fl.rounds == loop.rounds
    assert fl.battery is None
    np.testing.assert_allclose(fl.history_raw["accuracy"], loop.history_raw["accuracy"],
                               rtol=1e-5, atol=1e-6)
    lv, _ = ravel_pytree(loop.params)
    fv, _ = ravel_pytree(fl.params)
    np.testing.assert_allclose(np.asarray(fv), np.asarray(lv),
                               rtol=1e-4, atol=1e-5)


def test_fleet_baseline_multi_lane_matches_per_requester_loops(problem):
    """Several baseline sessions advance in ONE compiled program; each
    lane matches the loop oracle run on that lane's own roster."""
    from repro.core.federated import CFLLearner, DFLLearner

    task, own_train, own_test, fleet, states = problem
    half = (own_train[0][:len(own_train[0]) // 2],
            own_train[1][:len(own_train[1]) // 2])
    cfg = EnFedConfig(desired_accuracy=0.99, max_rounds=2, epochs=1,
                      batch_size=BATCH, seed=2)
    specs = [RequesterSpec(sh, own_test, fleet, copy.deepcopy(states))
             for sh in (own_train, half)]
    for method, learner in (("cfl", CFLLearner),
                            ("dfl", lambda t, d, te: DFLLearner(t, d, te, "mesh"))):
        result = run_fleet(task, specs, cfg, method=method)
        assert len(result.sessions) == 2
        for lane, sh in enumerate((own_train, half)):
            loop = learner(task, _baseline_client_data(sh, fleet, states),
                           own_test).run_config(cfg)
            fl = result.sessions[lane]
            assert fl.rounds == loop.rounds
            np.testing.assert_allclose(fl.history_raw["accuracy"],
                                       loop.history_raw["accuracy"],
                                       rtol=1e-5, atol=1e-6)
            lv, _ = ravel_pytree(loop.params)
            fv, _ = ravel_pytree(fl.params)
            np.testing.assert_allclose(np.asarray(fv), np.asarray(lv),
                                       rtol=1e-4, atol=1e-5)


def test_fleet_baseline_rejects_unknown_method_and_topology(problem):
    task, own_train, own_test, fleet, states = problem
    cfg = EnFedConfig(max_rounds=1, epochs=1, batch_size=BATCH)
    spec = RequesterSpec(own_train, own_test, fleet, states)
    with pytest.raises(ValueError):
        run_fleet(task, [spec], cfg, method="fedprox")
    with pytest.raises(ValueError):
        run_fleet(task, [spec], cfg, method="dfl", dfl_topology="torus")


def test_fleet_early_exit_executes_o_k_round_bodies(problem):
    """Every session stops by round 1 (trivial accuracy target); with
    max_rounds=32 the program must execute only the first round chunk —
    asserted via the executed-body trace, which is written in place by
    the rounds that actually ran."""
    task, own_train, own_test, fleet, states = problem
    cfg = EnFedConfig(desired_accuracy=0.05, max_rounds=32, epochs=1,
                      batch_size=BATCH, encrypt=False,
                      contributor_refresh_epochs=1)
    result = run_fleet(task, [RequesterSpec(own_train, own_test, fleet,
                                            copy.deepcopy(states))],
                       cfg, round_chunk=4)
    assert (result.rounds == 1).all()
    assert (result.stop_codes == 1).all()  # protocol.STOP_ACCURACY
    body = result.history_raw["round_executed"]
    assert body.shape == (cfg.max_rounds,)
    # O(k): at most one chunk of bodies ran, nothing near max_rounds
    assert body.sum() <= 4
    assert body[0] == 1.0 and (body[4:] == 0.0).all()
    # per-lane active mask agrees: only round 0 had a live lane
    assert result.history_raw["executed"][0].all()
    assert (result.history_raw["executed"][1:] == 0.0).all()


def test_fleet_round_chunk_does_not_change_results(problem):
    """Parity across chunk sizes, including a chunk that overshoots
    max_rounds (the in-chunk lax.cond masks the overhang)."""
    task, own_train, own_test, fleet, states = problem
    cfg = EnFedConfig(desired_accuracy=0.99, max_rounds=3, epochs=1,
                      batch_size=BATCH, encrypt=False,
                      contributor_refresh_epochs=1)
    results = [run_fleet(task, [RequesterSpec(own_train, own_test, fleet,
                                              copy.deepcopy(states))],
                         cfg, round_chunk=c) for c in (1, 2, 8)]
    ref = results[0].sessions[0]
    for res in results[1:]:
        fl = res.sessions[0]
        assert fl.rounds == ref.rounds and fl.stop_reason == ref.stop_reason
        np.testing.assert_allclose(fl.history_raw["accuracy"], ref.history_raw["accuracy"],
                                   rtol=1e-6)
        lv, _ = ravel_pytree(ref.params)
        fv, _ = ravel_pytree(fl.params)
        np.testing.assert_allclose(np.asarray(fv), np.asarray(lv), rtol=1e-6)
    with pytest.raises(ValueError):
        run_fleet(task, [RequesterSpec(own_train, own_test, fleet, states)],
                  cfg, round_chunk=0)
