"""Asynchronous cadence world: counter-based device round clocks.

``repro.core.cadence`` ends the lockstep round barrier: per-device
speed classes, duty cycles, transient offline windows and battery
pacing make each lane's round clock advance on its own tick steps.
These tests pin the three contracts the subsystem guarantees:

* the tick derivation is closed-form counter-based — traced and
  concrete evaluation agree bitwise, and a step's tick set does not
  depend on which other steps were queried;
* both engines derive the SAME asynchronous trajectory: bitwise round
  clocks / idle counts / membership masks / tick sets, allclose params,
  across static, mobility, and fault worlds — including kill-and-resume
  bit-identity with cadence on;
* ``cadence=None`` (and the degenerate always-tick config) reproduce
  the lockstep engines bit for bit: lockstep is a special case, not a
  separate code path.
"""

import copy
import glob
import os

import jax
import numpy as np
import pytest
from jax.flatten_util import ravel_pytree

from repro.core import (CadenceConfig, EnFedConfig, EnFedSession,
                        MobilityConfig, RequesterSpec, run_fleet)
from repro.core import cadence as cadence_mod
from repro.core.battery import BatteryState
from repro.core.faults import FaultConfig

from test_fleet_engine import BATCH, _build

_REQ = CadenceConfig().requester_id
_CONTRIB_IDS = list(range(3))       # make_fleet's device ids in _build


def _two_class_seed(pred):
    """The first two-speed-class cadence whose hashed world satisfies
    ``pred``.  Which stride a seed hashes to is up to the installed
    ``jax.random``, so the worlds below are chosen by the property the
    tests need, not pinned to a seed number."""
    return next(cc for cc in (CadenceConfig(n_speed_classes=2, seed=s)
                              for s in range(256)) if pred(cc))


# the REQUESTER on stride 2, ticking on odd event steps: it idles
# between its rounds
CC_SLOW_REQ = _two_class_seed(
    lambda cc: int(cadence_mod.speed_stride(cc, _REQ)) == 2
    and bool(cadence_mod.tick_mask(cc, 1, _REQ)))
# the requester on stride 1 while a contributor lands on stride 2 — the
# requester outpaces its STRAGGLERS (asserted below, not assumed)
CC_STRAGGLER = _two_class_seed(
    lambda cc: int(cadence_mod.speed_stride(cc, _REQ)) == 1
    and int(cadence_mod.speed_stride(cc, _CONTRIB_IDS).max()) == 2)


@pytest.fixture(scope="module")
def problem():
    return _build()


# ---------------------------------------------------------------------------
# the cadence derivation itself
# ---------------------------------------------------------------------------


def test_cadence_config_validates():
    with pytest.raises(ValueError):
        CadenceConfig(n_speed_classes=0)
    with pytest.raises(ValueError):
        CadenceConfig(duty_cycle=4, duty_on=0)
    with pytest.raises(ValueError):
        CadenceConfig(duty_cycle=4, duty_on=5)
    with pytest.raises(ValueError):
        CadenceConfig(p_offline=1.0)
    with pytest.raises(ValueError):
        CadenceConfig(pace_factor=0)
    with pytest.raises(ValueError):
        CadenceConfig(pace_battery_threshold=1.5)
    with pytest.raises(ValueError):
        CadenceConfig(idle_step_s=-0.1)


def test_tick_mask_traced_equals_concrete():
    """The jit/vmap evaluation the fleet engine runs must agree bitwise
    with the loop engine's concrete host-side calls."""
    cc = CadenceConfig(n_speed_classes=3, duty_cycle=4, duty_on=2,
                       p_offline=0.2, seed=11)
    ids = np.arange(1, 9, dtype=np.int32)
    traced = jax.jit(lambda t: cadence_mod.tick_mask(cc, t, ids))
    for t in range(12):
        np.testing.assert_array_equal(
            np.asarray(traced(t)),
            np.asarray(cadence_mod.tick_mask(cc, t, ids)))


def test_tick_mask_is_closed_form():
    """Counter-based world state: step t's ticks are a pure function of
    (seed, t, device) — per-step queries equal any batched/shuffled
    evaluation order, so no replay is ever needed."""
    cc = CadenceConfig(n_speed_classes=2, duty_cycle=3, duty_on=1,
                       p_offline=0.3, seed=4)
    ids = np.arange(1, 6, dtype=np.int32)
    forward = [np.asarray(cadence_mod.tick_mask(cc, t, ids))
               for t in range(10)]
    backward = [np.asarray(cadence_mod.tick_mask(cc, t, ids))
                for t in reversed(range(10))]
    np.testing.assert_array_equal(np.stack(forward),
                                  np.stack(backward[::-1]))
    # and a single device queried alone matches its column in the batch
    for t in (0, 3, 7):
        for j, d in enumerate(ids):
            assert bool(cadence_mod.tick_mask(cc, t, d)) == bool(forward[t][j])


def test_events_budget():
    # worst stride x duty ceiling x offline allowance
    assert cadence_mod.events_budget(CadenceConfig(), 7) == 7
    assert cadence_mod.events_budget(
        CadenceConfig(n_speed_classes=2, seed=3), 4) == 8
    assert cadence_mod.events_budget(
        CadenceConfig(n_speed_classes=2, duty_cycle=4, duty_on=2,
                      p_offline=0.1), 3) == 3 * 2 * 2 * 2
    assert cadence_mod.events_budget(
        CadenceConfig(max_events=11, n_speed_classes=5), 3) == 11


def test_stride_one_always_ticks():
    cc = CadenceConfig()   # one speed class, no duty/offline/pacing
    ids = np.arange(100, dtype=np.int32)
    for t in range(5):
        assert np.asarray(cadence_mod.tick_mask(cc, t, ids)).all()


# ---------------------------------------------------------------------------
# engine parity on async worlds
# ---------------------------------------------------------------------------


def _run_both(problem, cfg, battery_kw=None):
    task, own_train, own_test, fleet, states = problem
    bk = battery_kw or {}
    loop = EnFedSession(task, own_train, own_test, fleet,
                        copy.deepcopy(states), cfg,
                        battery=BatteryState(**bk)).run()
    spec = RequesterSpec(own_train=own_train, own_test=own_test,
                         neighborhood=fleet,
                         contributor_states=copy.deepcopy(states),
                         battery=BatteryState(**bk))
    fl = run_fleet(task, [spec], cfg).sessions[0]
    return loop, fl


def _assert_async_parity(loop, fl):
    """Bitwise on the async trajectory (clocks, idle counts, masks),
    allclose on the float metrics — the ISSUE's parity contract."""
    lh, fh = loop.history_raw, fl.history_raw
    assert fl.rounds == loop.rounds
    assert fl.stop_reason == loop.stop_reason
    assert lh["round_clock"] == fh["round_clock"]
    assert lh["idle_steps"] == fh["idle_steps"]
    np.testing.assert_allclose(fh["battery"], lh["battery"],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(fh["accuracy"], lh["accuracy"],
                               rtol=1e-5, atol=1e-6)
    for k in ("member_mask", "deliver_mask"):
        if k in lh:
            lm, fm = np.stack(lh[k]), np.stack(fh[k])
            np.testing.assert_array_equal(lm, fm[:, :lm.shape[1]])
            assert not fm[:, lm.shape[1]:].any()   # fleet N-padding only
    lv, _ = ravel_pytree(loop.params)
    fv, _ = ravel_pytree(fl.params)
    np.testing.assert_allclose(np.asarray(fv), np.asarray(lv),
                               rtol=1e-4, atol=1e-5)
    # the idle pricing went through the one shared helper identically
    assert abs(loop.report.times.t_com - fl.report.times.t_com) < 1e-9


def test_async_parity_static_requester_idles(problem):
    """Requester on stride 2: its clock skips every other event step and
    the idle windows are priced identically by both engines."""
    cfg = EnFedConfig(desired_accuracy=0.99, max_rounds=3, epochs=2,
                      batch_size=BATCH, encrypt=False,
                      contributor_refresh_epochs=1, cadence=CC_SLOW_REQ)
    loop, fl = _run_both(problem, cfg)
    _assert_async_parity(loop, fl)
    clock = loop.history_raw["round_clock"]
    assert clock == [1, 3, 5]                      # stride-2 requester
    assert loop.history_raw["idle_steps"] == [1, 1, 1]
    assert loop.report.times.t_com > 0             # idle seconds priced


def test_async_parity_static_stragglers_refresh_less(problem):
    """Requester on stride 1 with stride-2 contributors: straggler
    rounds provably happen (a signed contributor's tick is off on at
    least one executed step) and their resident wire images are
    aggregated as-is by both engines."""
    task, own_train, own_test, fleet, states = problem
    cfg = EnFedConfig(desired_accuracy=0.99, max_rounds=3, epochs=2,
                      batch_size=BATCH, encrypt=False,
                      contributor_refresh_epochs=1, cadence=CC_STRAGGLER)
    loop, fl = _run_both(problem, cfg)
    _assert_async_parity(loop, fl)
    ids = np.array([d.device_id for d in fleet], np.int32)
    straggled = sum(
        int((~np.asarray(cadence_mod.tick_mask(CC_STRAGGLER, t, ids))).sum())
        for t in loop.history_raw["round_clock"])
    assert straggled >= 1, "no straggler round exercised: pick a new seed"


def test_async_parity_duty_cycle_and_offline(problem):
    cc = CadenceConfig(n_speed_classes=2, duty_cycle=3, duty_on=2,
                       p_offline=0.15, seed=1)
    cfg = EnFedConfig(desired_accuracy=0.99, max_rounds=3, epochs=1,
                      batch_size=BATCH, encrypt=False,
                      contributor_refresh_epochs=1, cadence=cc)
    loop, fl = _run_both(problem, cfg)
    _assert_async_parity(loop, fl)
    assert max(loop.history_raw["idle_steps"]) >= 2   # real duty gaps


def test_async_parity_fault_world(problem):
    """Fault weather keys on the global event step; delivered/stale
    masks stay bitwise identical across engines under cadence."""
    cfg = EnFedConfig(
        desired_accuracy=0.99, max_rounds=3, epochs=1, batch_size=BATCH,
        encrypt=False, contributor_refresh_epochs=1, cadence=CC_STRAGGLER,
        faults=FaultConfig(p_drop=0.3, p_stale=0.25, max_retries=1, seed=7))
    loop, fl = _run_both(problem, cfg)
    _assert_async_parity(loop, fl)


def test_async_parity_mobility_world(problem):
    """Mobility kinematics key on the global event step; the membership
    trajectory stays bitwise identical across engines under cadence."""
    cfg = EnFedConfig(desired_accuracy=0.99, max_rounds=3, epochs=1,
                      batch_size=BATCH, encrypt=False,
                      contributor_refresh_epochs=1, cadence=CC_SLOW_REQ,
                      mobility=MobilityConfig(seed=3))
    loop, fl = _run_both(problem, cfg)
    _assert_async_parity(loop, fl)
    assert loop.history_raw["round_clock"] == [1, 3, 5]


def test_async_parity_battery_pacing(problem):
    """The one state-coupled rule: crossing the pacing threshold slows
    the requester's clock mid-session, identically in both engines."""
    cc = CadenceConfig(pace_battery_threshold=0.87, pace_factor=2, seed=2)
    cfg = EnFedConfig(desired_accuracy=0.99, max_rounds=4, epochs=1,
                      batch_size=BATCH, encrypt=False,
                      contributor_refresh_epochs=1, battery_threshold=0.05,
                      cadence=cc)
    loop, fl = _run_both(problem, cfg,
                         battery_kw=dict(capacity_j=4.0, level=0.9))
    _assert_async_parity(loop, fl)
    # unpaced round 0 at t=0, then the drained battery halves the clock
    assert loop.history_raw["round_clock"][0] == 0
    assert max(loop.history_raw["idle_steps"][1:]) >= 1


# ---------------------------------------------------------------------------
# lockstep is a special case, not a fork
# ---------------------------------------------------------------------------


def test_degenerate_cadence_is_lockstep_bitwise(problem):
    """An always-tick cadence (one speed class, no duty/offline/pacing,
    budget == round budget) reproduces cadence=None bit for bit in both
    engines — the async code path contains the lockstep protocol as its
    fixed point."""
    base = dict(desired_accuracy=0.99, max_rounds=2, epochs=1,
                batch_size=BATCH, encrypt=False,
                contributor_refresh_epochs=1)
    off = EnFedConfig(**base)
    on = EnFedConfig(**base, cadence=CadenceConfig(max_events=2))
    for engine in ("loop", "fleet"):
        a = _engine_run(problem, off, engine)
        b = _engine_run(problem, on, engine)
        pa, _ = ravel_pytree(a.params)
        pb, _ = ravel_pytree(b.params)
        assert np.array_equal(np.asarray(pa), np.asarray(pb)), engine
        np.testing.assert_array_equal(a.history_raw["battery"],
                                      b.history_raw["battery"])
        np.testing.assert_array_equal(a.history_raw["accuracy"],
                                      b.history_raw["accuracy"])
        assert b.history_raw["round_clock"] == [0, 1]   # t == r exactly
        assert b.history_raw["idle_steps"] == [0, 0]


def _engine_run(problem, cfg, engine):
    task, own_train, own_test, fleet, states = problem
    if engine == "loop":
        return EnFedSession(task, own_train, own_test, fleet,
                            copy.deepcopy(states), cfg,
                            battery=BatteryState()).run()
    spec = RequesterSpec(own_train=own_train, own_test=own_test,
                         neighborhood=fleet,
                         contributor_states=copy.deepcopy(states),
                         battery=BatteryState())
    return run_fleet(task, [spec], cfg).sessions[0]


def test_cadence_is_enfed_only(problem):
    task, own_train, own_test, fleet, states = problem
    cfg = EnFedConfig(max_rounds=1, cadence=CadenceConfig())
    spec = RequesterSpec(own_train=own_train, own_test=own_test,
                         neighborhood=fleet,
                         contributor_states=copy.deepcopy(states))
    with pytest.raises(ValueError, match="cadence"):
        run_fleet(task, [spec], cfg, method="dfl")


# ---------------------------------------------------------------------------
# kill-and-resume with cadence on
# ---------------------------------------------------------------------------


def _kill_after(ckpt_dir, keep_step):
    removed = 0
    for f in glob.glob(os.path.join(ckpt_dir, "step_*.npz")):
        if int(os.path.basename(f)[5:13]) > keep_step:
            os.remove(f)
            removed += 1
    assert removed > 0, "nothing to kill: checkpointing did not run"


def _assert_resume_identical(full, res):
    fp, _ = ravel_pytree(full.params)
    rp, _ = ravel_pytree(res.params)
    assert np.array_equal(np.asarray(fp), np.asarray(rp))
    assert res.rounds == full.rounds
    assert res.stop_reason == full.stop_reason
    fh, rh = full.history_raw, res.history_raw
    assert fh["round_clock"] == rh["round_clock"]
    assert fh["idle_steps"] == rh["idle_steps"]
    np.testing.assert_array_equal(fh["battery"], rh["battery"])
    assert full.report.times.t_com == res.report.times.t_com


def test_loop_resume_with_cadence(problem, tmp_path):
    """Killed-and-resumed == uninterrupted, with the event clock and the
    accumulated idle run restored from the checkpoint payload."""
    task, own_train, own_test, fleet, states = problem
    cfg = EnFedConfig(desired_accuracy=0.99, max_rounds=4, epochs=1,
                      batch_size=BATCH, encrypt=False,
                      contributor_refresh_epochs=1, cadence=CC_SLOW_REQ)

    def run(**kw):
        return EnFedSession(task, own_train, own_test, fleet,
                            copy.deepcopy(states), cfg,
                            battery=BatteryState()).run(**kw)

    full = run()
    d = str(tmp_path / "loop")
    run(checkpoint_dir=d)
    _kill_after(d, 2)
    _assert_resume_identical(full, run(resume_from=d))


def test_fleet_resume_with_cadence(problem, tmp_path):
    """The named carry's clock/idle fields round-trip through the
    checkpoint at chunk boundaries (event-step granularity)."""
    task, own_train, own_test, fleet, states = problem
    cfg = EnFedConfig(desired_accuracy=0.99, max_rounds=4, epochs=1,
                      batch_size=BATCH, encrypt=False,
                      contributor_refresh_epochs=1, cadence=CC_SLOW_REQ)

    def run(**kw):
        spec = RequesterSpec(own_train=own_train, own_test=own_test,
                             neighborhood=fleet,
                             contributor_states=copy.deepcopy(states),
                             battery=BatteryState())
        return run_fleet(task, [spec], cfg, round_chunk=2, **kw).sessions[0]

    full = run()
    d = str(tmp_path / "fleet")
    run(checkpoint_dir=d)
    _kill_after(d, 2)
    _assert_resume_identical(full, run(resume_from=d))
