"""The method registry: ``"enfed"``, ``"dfl"``, ``"cfl"``, ``"cloud"``.

Each registered runner maps ``(world, method, execution) -> RunResult``
under the shared contract that makes comparisons meaningful:

* it trains on the world's data/models as-is (``world.fresh_requesters``
  copies keep runs independent),
* every energy/time figure comes from the world's ONE
  :class:`repro.core.energy.CostModel`, with ``model_bytes`` priced
  through the shared :func:`repro.core.energy.update_wire_bytes` helper
  — so the ``MethodSpec.compress`` knob lowers transmission/crypto
  energy consistently for enfed AND the dfl/cfl baselines (cloud ships
  raw data, not model updates, and is unaffected),
* the protocol knobs are read from the :class:`MethodSpec`'s
  EnFedConfig-shaped surface — the baselines have no private kwargs.

New workloads plug in with :func:`register_method` instead of growing a
fourth ad-hoc entrypoint signature.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Callable, Dict, Tuple

from repro.api.result import RunResult
from repro.api.specs import ExecutionSpec, MethodSpec, WorldSpec
from repro.core import federated, protocol
from repro.core.energy import update_wire_bytes
from repro.core.rounds import EnFedSession, SessionResult
from repro.telemetry.spans import Timeline
from repro.utils.tree import tree_bytes, tree_size

MethodRunner = Callable[[WorldSpec, MethodSpec, ExecutionSpec], RunResult]

_REGISTRY: Dict[str, MethodRunner] = {}


def register_method(name: str):
    """Decorator: add a runner under ``name`` (e.g. a new baseline)."""

    def deco(fn: MethodRunner) -> MethodRunner:
        _REGISTRY[name] = fn
        return fn

    return deco


def method_names() -> Tuple[str, ...]:
    return tuple(_REGISTRY)


def get_runner(name: str) -> MethodRunner:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown method {name!r}; registered: {sorted(_REGISTRY)}") from None


def _warn_if_mobility_ignored(world: WorldSpec, name: str) -> None:
    """The host-side baselines have no opportunistic-world execution:
    they train their full static client set every round.  Comparing them
    against EnFed-under-churn is apples-to-oranges, so dropping the
    world's mobility axis must never be silent."""
    if world.mobility is not None:
        warnings.warn(
            f"method {name!r} ignores world.mobility (no opportunistic-"
            "world execution: its full static client set trains every "
            "round); a compare() against EnFed-under-churn mixes a churn "
            "world with static baselines", stacklevel=3)


def _warn_if_cadence_ignored(method: MethodSpec, name: str) -> None:
    """The baselines have no per-device round clock — dfl/cfl sweep
    every node each round and cloud has no rounds at all.  Same
    never-silent rule as the mobility axis: asking a baseline to run an
    async-cadence world warns, and the knob is stripped before the run
    (the fleet engine refuses cadence for non-enfed methods)."""
    if method.cadence is not None:
        warnings.warn(
            f"method {name!r} ignores MethodSpec.cadence (async device "
            "round clocks are enfed-only: baselines sweep their full "
            "client set every round); comparing against EnFed-under-"
            "cadence mixes an async world with lockstep baselines",
            stacklevel=3)


def _strip_cadence(method: MethodSpec) -> MethodSpec:
    return (dataclasses.replace(method, cadence=None)
            if method.cadence is not None else method)


def _warn_if_adversary_ignored(method: MethodSpec, name: str) -> None:
    """Byzantine contributors, the robust aggregation statistic, and
    staleness decay are enfed protocol knobs (Phase.DELIVER/AGGREGATE);
    the baselines' loop oracles define their aggregation semantics
    without them.  Same never-silent rule as the mobility/cadence axes:
    asking a baseline to run a Byzantine world warns, and the knobs are
    stripped before the run (the fleet baselines refuse them)."""
    if (method.adversary is not None or method.robust != "none"
            or method.staleness_gamma != 1.0):
        warnings.warn(
            f"method {name!r} ignores MethodSpec.adversary/robust/"
            "staleness_gamma (Byzantine contributors and robust "
            "aggregation are enfed-only); comparing against "
            "EnFed-under-attack mixes an adversarial world with honest "
            "baselines", stacklevel=3)


def _strip_adversary(method: MethodSpec) -> MethodSpec:
    if (method.adversary is None and method.robust == "none"
            and method.staleness_gamma == 1.0):
        return method
    return dataclasses.replace(method, adversary=None, robust="none",
                               staleness_gamma=1.0)


def _warn_if_checkpoint_ignored(execution: ExecutionSpec, name: str) -> None:
    """Resumable round state is an enfed contract (the baselines' loop
    oracles have no serialized mid-run state).  Same never-silent rule
    as the mobility axis: asking a baseline to checkpoint must warn, not
    quietly do nothing."""
    if execution.checkpoint_dir or execution.resume_from:
        warnings.warn(
            f"method {name!r} ignores ExecutionSpec checkpointing "
            "(checkpoint_dir/resume_from are enfed-only: baselines have "
            "no resumable round-state contract)", stacklevel=3)


def _warn_if_trace_fleet_only(execution: ExecutionSpec, name: str) -> None:
    """``TraceConfig.hlo_stats`` compiles THE fleet program — the loop
    engine (and the host-side baselines) has no such program.
    Never-silent rule: asking for it on a loop run warns instead of
    quietly reporting nothing.  The other selections (events_jsonl,
    chrome_trace, jax_profiler_dir) work on every engine and stay
    silent."""
    tr = execution.trace
    if tr is not None and getattr(tr, "hlo_stats", False):
        warnings.warn(
            f"{name} run ignores TraceConfig.hlo_stats (fleet-engine-only: "
            "it compiles the fleet program); event/timeline exports and "
            "the profiler still apply", stacklevel=3)


def _baseline_model_bytes(params, cfg) -> int:
    """One update's wire bytes for a loop cfl/dfl session — the same
    ``update_wire_bytes`` call ``_run_fleet_baseline`` prices its views
    with, so the two engines' event streams carry identical
    ``wire_bytes``."""
    return update_wire_bytes(tree_size(params), encrypt=False,
                             compress=getattr(cfg, "compress", None),
                             raw_bytes=tree_bytes(params))


def _baseline_session(res: "federated.BaselineResult", *, target: float,
                      n_contributors: float,
                      model_bytes: int = 0) -> SessionResult:
    """A BaselineResult in the per-requester SessionResult schema."""
    stopped = res.accuracy >= target
    stop = (protocol.STOP_ACCURACY if stopped else protocol.STOP_MAX_ROUNDS)
    return SessionResult(
        accuracy=res.accuracy, rounds=res.rounds,
        n_contributors=n_contributors, report=res.report, battery=None,
        history=res.history, stop_reason=protocol.stop_reason_name(stop),
        params=res.params, model_bytes=model_bytes)


@register_method("enfed")
def run_enfed(world: WorldSpec, method: MethodSpec,
              execution: ExecutionSpec) -> RunResult:
    """EnFed Algorithm 1 — the only method with two engines; the
    ExecutionSpec picks which and tunes the compiled one."""
    from repro.core import fleet as fleet_mod

    cfg = method.to_enfed_config(world)
    cost = world.cost_model
    tl = Timeline()
    with tl.span("copy_world"):
        reqs = world.fresh_requesters()
    if execution.engine == "fleet":
        fr = fleet_mod.run_fleet(
            world.task, reqs, cfg, cost_model=cost,
            use_pallas=execution.use_pallas, interpret=execution.interpret,
            round_chunk=execution.round_chunk,
            checkpoint_dir=execution.checkpoint_dir,
            checkpoint_every=execution.checkpoint_every,
            resume_from=execution.resume_from,
            timeline=tl, trace=execution.trace)
        with tl.span("assemble"):
            return RunResult.from_sessions(
                "enfed", "fleet", fr.sessions, cost_model=cost,
                total_energy_j=fr.total_energy_j, raw=fr,
                timeline=tl, hlo_stats=fr.hlo_stats)
    _warn_if_trace_fleet_only(execution, "loop-engine enfed")

    def _sub(root, i):
        # multi-requester loop runs checkpoint per session: requester
        # i's state lives under <root>/req<i> (a 1-requester world keeps
        # the bare directory, so loop and fleet runs can share paths)
        if not root or len(reqs) == 1:
            return root
        import os
        return os.path.join(root, f"req{i}")

    sessions = []
    for i, r in enumerate(reqs):
        # requester i walks as device mobility.requester_id + i and rolls
        # fault dice as faults.requester_id + i — the fleet engine's lane
        # conventions — so ExecutionSpec.engine can never change which
        # world a requester experiences
        cfg_i = cfg
        if cfg.mobility is not None and i > 0:
            cfg_i = dataclasses.replace(
                cfg_i, mobility=dataclasses.replace(
                    cfg.mobility,
                    requester_id=cfg.mobility.requester_id + i))
        if cfg.faults is not None and i > 0:
            cfg_i = dataclasses.replace(
                cfg_i, faults=dataclasses.replace(
                    cfg.faults,
                    requester_id=cfg.faults.requester_id + i))
        if cfg.cadence is not None and i > 0:
            cfg_i = dataclasses.replace(
                cfg_i, cadence=dataclasses.replace(
                    cfg.cadence,
                    requester_id=cfg.cadence.requester_id + i))
        if cfg.adversary is not None and i > 0:
            cfg_i = dataclasses.replace(
                cfg_i, adversary=dataclasses.replace(
                    cfg.adversary,
                    requester_id=cfg.adversary.requester_id + i))
        sessions.append(EnFedSession(
            world.task, r.own_train, r.own_test,
            r.neighborhood, r.contributor_states,
            cfg_i, cost_model=cost, battery=r.battery).run(
                checkpoint_dir=_sub(execution.checkpoint_dir, i),
                checkpoint_every=execution.checkpoint_every,
                resume_from=_sub(execution.resume_from, i), timeline=tl))
    with tl.span("assemble"):
        return RunResult.from_sessions("enfed", "loop", sessions,
                                       cost_model=cost, timeline=tl)


def _run_baseline_fleet(world: WorldSpec, method: MethodSpec,
                        execution: ExecutionSpec, name: str) -> RunResult:
    """dfl/cfl as traced protocol variants of the compiled fleet engine
    (``run_fleet(method=...)``) — the rows a large-R ``compare()`` gets
    are simulated by the same jit program enfed runs in, not
    extrapolated from loop sessions.  Baselines re-init node params and
    write nothing back, so the world's requesters are used read-only."""
    from repro.core import fleet as fleet_mod

    cfg = method.to_enfed_config(world)
    cost = world.cost_model
    tl = Timeline()
    fr = fleet_mod.run_fleet(
        world.task, world.requesters, cfg, cost_model=cost,
        use_pallas=execution.use_pallas, interpret=execution.interpret,
        round_chunk=execution.round_chunk, method=name,
        dfl_topology=method.topology,
        timeline=tl, trace=execution.trace)
    return RunResult.from_sessions(name, "fleet", fr.sessions,
                                   cost_model=cost,
                                   total_energy_j=fr.total_energy_j, raw=fr,
                                   timeline=tl, hlo_stats=fr.hlo_stats)


@register_method("cfl")
def run_cfl(world: WorldSpec, method: MethodSpec,
            execution: ExecutionSpec) -> RunResult:
    """Centralized FL baseline, per requesting device (client 0)."""
    _warn_if_mobility_ignored(world, "cfl")
    _warn_if_checkpoint_ignored(execution, "cfl")
    _warn_if_cadence_ignored(method, "cfl")
    method = _strip_cadence(method)
    _warn_if_adversary_ignored(method, "cfl")
    method = _strip_adversary(method)
    if execution.engine == "fleet":
        return _run_baseline_fleet(world, method, execution, "cfl")
    _warn_if_trace_fleet_only(execution, "cfl")
    cfg = method.to_enfed_config(world)
    cost = world.cost_model
    sessions = []
    # baselines re-init their node params, so the world's contributor
    # states are read-only here — no fresh copies needed
    for i, r in enumerate(world.requesters):
        data = world.client_data(i)
        res = federated.CFLLearner(world.task, data, r.own_test,
                                   cost_model=cost).run_config(cfg)
        sessions.append(_baseline_session(
            res, target=cfg.desired_accuracy, n_contributors=len(data) - 1,
            model_bytes=_baseline_model_bytes(res.params, cfg)))
    return RunResult.from_sessions("cfl", "loop", sessions, cost_model=cost,
                                   timeline=Timeline())


@register_method("dfl")
def run_dfl(world: WorldSpec, method: MethodSpec,
            execution: ExecutionSpec) -> RunResult:
    """Decentralized FL baseline over ``method.topology`` (mesh|ring)."""
    _warn_if_mobility_ignored(world, "dfl")
    _warn_if_checkpoint_ignored(execution, "dfl")
    _warn_if_cadence_ignored(method, "dfl")
    method = _strip_cadence(method)
    _warn_if_adversary_ignored(method, "dfl")
    method = _strip_adversary(method)
    if execution.engine == "fleet":
        return _run_baseline_fleet(world, method, execution, "dfl")
    _warn_if_trace_fleet_only(execution, "dfl")
    cfg = method.to_enfed_config(world)
    cost = world.cost_model
    sessions = []
    for i, r in enumerate(world.requesters):
        data = world.client_data(i)
        res = federated.DFLLearner(world.task, data, r.own_test,
                                   method.topology,
                                   cost_model=cost).run_config(cfg)
        sessions.append(_baseline_session(
            res, target=cfg.desired_accuracy, n_contributors=len(data) - 1,
            model_bytes=_baseline_model_bytes(res.params, cfg)))
    return RunResult.from_sessions("dfl", "loop", sessions, cost_model=cost,
                                   timeline=Timeline())


@register_method("cloud")
def run_cloud(world: WorldSpec, method: MethodSpec,
              execution: ExecutionSpec) -> RunResult:
    """The §IV-G no-FL baseline: ship raw data to the cloud, wait, get
    the result back.  Device-side cost via ``CostModel.cloud_session``."""
    _warn_if_mobility_ignored(world, "cloud")
    _warn_if_checkpoint_ignored(execution, "cloud")
    _warn_if_cadence_ignored(method, "cloud")
    method = _strip_cadence(method)
    _warn_if_adversary_ignored(method, "cloud")
    method = _strip_adversary(method)
    _warn_if_trace_fleet_only(execution, "cloud")
    cfg = method.to_enfed_config(world)
    cost = world.cost_model
    sessions = []
    for i, r in enumerate(world.requesters):
        res = federated.cloud_only_config(world.task, world.pooled(i),
                                          r.own_test, cfg, cost_model=cost)
        # cloud ships raw data, not model updates: no per-round wire
        sessions.append(_baseline_session(
            res, target=cfg.desired_accuracy, n_contributors=0.0))
    return RunResult.from_sessions("cloud", "loop", sessions, cost_model=cost,
                                   timeline=Timeline())
