"""Determinism of the opportunistic world (repro.core.mobility).

The mobility subsystem is parity-critical the same way the derived
minibatch schedule is: both engines must see the SAME world.  These
tests pin down (a) the counter-based kinematics — closed-form in
(seed, round, device), identical under tracing, prefix-stable under
candidate padding; (b) the re-negotiation semantics — top-n_max by
utility, battery-floor releases, arrival undercutting; and (c) fleet
runs being invariant to ``round_chunk`` with mobility enabled.  The
full train-loop churn parity (params/battery/masks, loop vs fleet)
lives in tests/test_fleet_engine.py.
"""

import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import mobility
from repro.core.mobility import MobilityConfig


# ---------------------------------------------------------------------------
# kinematics: counter-based, traceable, prefix-stable
# ---------------------------------------------------------------------------


def test_positions_inside_arena_and_deterministic():
    mob = MobilityConfig(arena_m=150.0, leg_rounds=3, seed=11)
    traj = np.asarray(mobility.trajectory(mob, 7, 20))
    assert traj.shape == (20, 2)
    assert (traj >= 0.0).all() and (traj <= 150.0).all()
    again = np.asarray(mobility.trajectory(mob, 7, 20))
    np.testing.assert_array_equal(traj, again)


def test_static_mode_pins_devices():
    mob = MobilityConfig(mode="static", seed=5)
    traj = np.asarray(mobility.trajectory(mob, 3, 12))
    assert (traj == traj[0]).all()


def test_waypoint_interpolation_hits_leg_endpoints():
    """Round k*leg_rounds sits exactly ON waypoint k; in between the
    device moves linearly — the closed-form discretized random-waypoint."""
    mob = MobilityConfig(leg_rounds=4, seed=2)
    traj = np.asarray(mobility.trajectory(mob, 9, 13))
    w0, w4, w8 = traj[0], traj[4], traj[8]
    # interior rounds of a leg interpolate its endpoints
    np.testing.assert_allclose(traj[2], 0.5 * (w0 + w4), rtol=1e-5)
    np.testing.assert_allclose(traj[6], 0.5 * (w4 + w8), rtol=1e-5)
    assert not np.allclose(w0, w4), "waypoints differ"


def test_traced_round_matches_concrete_round():
    """The fleet engine queries positions with a TRACED round number
    inside its compiled loop; the loop engine passes python ints.  Same
    value, same position — the schedule-style parity keystone."""
    mob = MobilityConfig(leg_rounds=3, seed=9)
    for r in (0, 1, 5, 11):
        traced = jax.jit(lambda rr: mobility.device_position(mob, 3, rr))(
            jnp.int32(r))
        host = mobility.device_position(mob, 3, r)
        np.testing.assert_array_equal(np.asarray(traced), np.asarray(host))


def test_positions_prefix_stable_under_device_padding():
    """Each device's trajectory hashes from its own id alone: adding
    candidate lanes (fleet padding) never moves existing devices."""
    mob = MobilityConfig(seed=4)
    small = np.asarray(mobility.device_positions(mob, np.arange(3), 6))
    big = np.asarray(mobility.device_positions(mob, np.arange(8), 6))
    np.testing.assert_array_equal(small, big[:3])


# ---------------------------------------------------------------------------
# re-negotiation semantics
# ---------------------------------------------------------------------------


def _membership(mob, r, ids, level, base_util, n_max, cand_mask=None):
    ids = np.asarray(ids, np.int32)
    cand_mask = np.ones(ids.shape, bool) if cand_mask is None else cand_mask
    member, rank, util = mobility.membership_step(
        mob, r, mob.requester_id, ids, cand_mask,
        np.asarray(base_util, np.float32), np.asarray(level, np.float32),
        n_max)
    return np.asarray(member), np.asarray(rank), np.asarray(util)


def test_membership_caps_at_n_max_by_utility():
    # everyone in range (static world, huge radius), utility ordered 3>1>0>2
    mob = MobilityConfig(mode="static", radio_range_m=1e6, seed=0)
    base = np.array([0.3, 0.5, 0.1, 0.9], np.float32)
    member, rank, _ = _membership(mob, 0, np.arange(4), np.ones(4), base, 2)
    assert member.tolist() == [False, True, False, True]
    assert rank[3] == 0 and rank[1] == 1


def test_membership_releases_below_battery_floor():
    mob = MobilityConfig(mode="static", radio_range_m=1e6, seed=0,
                         battery_floor=0.25)
    base = np.array([0.9, 0.8, 0.7], np.float32)
    level = np.array([0.2, 0.9, 0.9], np.float32)   # best device is flat
    member, _, _ = _membership(mob, 0, np.arange(3), level, base, 3)
    assert member.tolist() == [False, True, True]


def test_membership_undercut_by_higher_utility_arrival():
    """With full slots, an eligible higher-utility device displaces the
    weakest member (contract-theory undercutting)."""
    mob = MobilityConfig(mode="static", radio_range_m=1e6, seed=0)
    base = np.array([0.4, 0.5, 0.95], np.float32)
    # device 2 (best) ineligible -> 0 and 1 fill both slots
    m0, _, _ = _membership(mob, 0, np.arange(3), [0.9, 0.9, 0.0], base, 2)
    assert m0.tolist() == [True, True, False]
    # device 2 arrives (battery back) -> weakest member (0) is displaced
    m1, _, _ = _membership(mob, 0, np.arange(3), [0.9, 0.9, 0.9], base, 2)
    assert m1.tolist() == [False, True, True]


def test_membership_prefix_stable_under_candidate_padding():
    """Fleet lanes are padded to the widest candidate pool; padded lanes
    (cand_mask False) must never alter the real lanes' membership —
    mirroring the schedule's prefix-stability guarantee."""
    mob = MobilityConfig(radio_range_m=120.0, leg_rounds=2, seed=3)
    base = np.array([0.6, 0.4, 0.8], np.float32)
    level = np.array([0.9, 0.8, 0.7], np.float32)
    for r in range(6):
        m_small, _, _ = _membership(mob, r, np.arange(3), level, base, 2)
        m_big, _, _ = _membership(
            mob, r, np.arange(6),
            np.concatenate([level, np.ones(3, np.float32)]),
            np.concatenate([base, np.full(3, 99.0, np.float32)]), 2,
            cand_mask=np.array([1, 1, 1, 0, 0, 0], bool))
        np.testing.assert_array_equal(m_small, m_big[:3])
        assert not m_big[3:].any()


def test_membership_ties_break_by_lane_index():
    mob = MobilityConfig(mode="static", radio_range_m=1e6, seed=0)
    base = np.full(4, 0.5, np.float32)
    member, rank, _ = _membership(mob, 0, np.arange(4), np.ones(4), base, 2)
    assert member.tolist() == [True, True, False, False]
    assert rank.tolist() == [0, 1, 2, 3]


def test_membership_events_counts_joins_and_leaves():
    trace = np.array([[1, 1, 0], [1, 0, 1], [1, 0, 1], [0, 0, 1]], bool)
    joins, leaves = mobility.membership_events(trace)
    assert joins == 1 and leaves == 2


# ---------------------------------------------------------------------------
# fleet integration: chunk invariance + engine parity of the world
# ---------------------------------------------------------------------------


def _tiny_problem(n_contrib=4, n_samples=260, seed=0):
    from repro.core import SupervisedTask, make_fleet
    from repro.data import (CaloriesDatasetConfig, dirichlet_partition,
                            make_calories_tabular)
    from repro.models import MLPClassifier, MLPClassifierConfig

    x, y = make_calories_tabular(CaloriesDatasetConfig(num_samples=n_samples))
    task = SupervisedTask(MLPClassifier(MLPClassifierConfig(8, (8,), 5)), lr=3e-3)
    parts = dirichlet_partition(y, num_clients=n_contrib + 1, alpha=100.0, seed=seed)
    shards = [(x[p], y[p]) for p in parts]
    own_x, own_y = shards[0]
    n = int(len(own_x) * 0.8)
    fleet = make_fleet(n_contrib, seed=1, p_has_model=1.0)
    states = {}
    for i, dev in enumerate(fleet):
        dev.reservation_price = 0.4
        states[dev.device_id] = {"params": task.init(seed=10 + i),
                                 "data": shards[i + 1]}
    return (task, (own_x[:n], own_y[:n]), (own_x[n:], own_y[n:]), fleet,
            states)


def test_fleet_mobility_round_chunk_invariance():
    """The churn trajectory (membership masks AND params) is an invariant
    of the world, not of the early-exit chunking."""
    from jax.flatten_util import ravel_pytree

    from repro.core import EnFedConfig, RequesterSpec, run_fleet

    task, own_train, own_test, fleet, states = _tiny_problem()
    cfg = EnFedConfig(desired_accuracy=0.99, max_rounds=5, epochs=1,
                      batch_size=16, encrypt=False, n_max=3,
                      contributor_refresh_epochs=1,
                      mobility=MobilityConfig(radio_range_m=110.0,
                                              leg_rounds=2, seed=3))
    results = [run_fleet(task, [RequesterSpec(own_train, own_test, fleet,
                                              copy.deepcopy(states))],
                         cfg, round_chunk=c) for c in (1, 3, 8)]
    ref = results[0]
    for res in results[1:]:
        np.testing.assert_array_equal(res.history_raw["member"],
                                      ref.history_raw["member"])
        assert res.sessions[0].rounds == ref.sessions[0].rounds
        rv, _ = ravel_pytree(ref.sessions[0].params)
        fv, _ = ravel_pytree(res.sessions[0].params)
        np.testing.assert_allclose(np.asarray(fv), np.asarray(rv), rtol=1e-6)


def test_loop_and_fleet_derive_identical_world():
    """Same seed => identical membership masks and battery trajectories
    across the two engines, independently of training tolerances."""
    from repro.core import EnFedConfig, EnFedSession, RequesterSpec, run_fleet

    task, own_train, own_test, fleet, states = _tiny_problem()
    cfg = EnFedConfig(desired_accuracy=0.99, max_rounds=5, epochs=1,
                      batch_size=16, encrypt=False, n_max=2,
                      contributor_refresh_epochs=1,
                      mobility=MobilityConfig(radio_range_m=90.0,
                                              leg_rounds=2, seed=7))
    loop = EnFedSession(task, own_train, own_test, fleet,
                        copy.deepcopy(states), cfg).run()
    fl = run_fleet(task, [RequesterSpec(own_train, own_test, fleet,
                                        copy.deepcopy(states))], cfg).sessions[0]
    assert fl.rounds == loop.rounds
    np.testing.assert_array_equal(np.array(loop.history_raw["member_mask"]),
                                  np.array(fl.history_raw["member_mask"]))
    np.testing.assert_allclose(fl.history_raw["battery"], loop.history_raw["battery"],
                               rtol=1e-5, atol=1e-6)


def test_mobility_config_validation():
    with pytest.raises(AssertionError):
        MobilityConfig(mode="teleport")
    with pytest.raises(AssertionError):
        MobilityConfig(leg_rounds=0)


# ---------------------------------------------------------------------------
# launch.mesh imports on the installed toolchain
# ---------------------------------------------------------------------------


def test_launch_mesh_imports_on_pinned_jax():
    """repro.launch.mesh imports on the jax the CI pins, and a mesh
    larger than the host fails loudly on the device COUNT."""
    from repro.launch import mesh

    with pytest.raises(RuntimeError, match="devices"):
        mesh.make_production_mesh()
