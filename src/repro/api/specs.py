"""Declarative experiment specs — the orthogonal axes of one run.

The legacy entrypoints tangle three concerns into incompatible call
conventions: *what world* the session runs in (requesters, neighbors,
contributor states, mobility, cost model, batteries), *which method*
trains (EnFed vs the paper's DFL/CFL/cloud baselines and their protocol
knobs), and *how it executes* (loop vs fleet engine, Pallas interpret
mode, early-exit chunking).  This module splits them:

* :class:`WorldSpec` — the simulated world, shared verbatim across every
  method of a comparison (that is what makes the paper's Table-style
  reductions meaningful).
* :class:`MethodSpec` — a method name from the registry
  (``repro.api.methods``) plus the protocol knobs, mapped 1:1 onto
  :class:`repro.core.rounds.EnFedConfig` so baselines consume the SAME
  configuration surface as EnFed.
* :class:`ExecutionSpec` — engine selection and engine tuning knobs;
  changing it must never change the simulated outcome, only how fast it
  is computed (parity-tested in ``tests/test_api.py``).
"""

from __future__ import annotations

import copy
import dataclasses
from typing import Dict, List, Optional, Union

import numpy as np

from repro.core.adversary import AdversaryConfig
from repro.core.battery import BatteryState
from repro.core.cadence import CadenceConfig
from repro.core.energy import CostModel
from repro.core.faults import FaultConfig
from repro.core.fleet import RequesterSpec
from repro.core.mobility import MobilityConfig
from repro.core.rounds import EnFedConfig
from repro.core.topology import AggregationStrategy


@dataclasses.dataclass
class WorldSpec:
    """The simulated world: who exists, what data/models/batteries they
    hold, how they move, and what everything costs.

    ``requesters[0]`` is "the requesting device" of the paper's
    comparisons; baselines that model a single participating device
    (CFL/DFL/cloud) are evaluated from its perspective.  ``seed`` drives
    every derivation (schedules, keys, kinematics) so two runs on one
    ``WorldSpec`` see the identical world.
    """

    task: object                          # SupervisedTask-like (init/fit/evaluate)
    requesters: List[RequesterSpec]
    cost_model: CostModel = dataclasses.field(default_factory=CostModel)
    mobility: Optional[MobilityConfig] = None
    pooled_train: Optional[tuple] = None  # cloud baseline corpus (default: all shards)
    seed: int = 0

    @classmethod
    def single(cls, task, own_train, own_test, neighborhood,
               contributor_states: Dict[int, dict], *,
               battery: Optional[BatteryState] = None,
               cost_model: Optional[CostModel] = None,
               mobility: Optional[MobilityConfig] = None,
               pooled_train: Optional[tuple] = None,
               seed: int = 0) -> "WorldSpec":
        """The common one-requester world, from ``EnFedSession``-style args."""
        return cls(task=task,
                   requesters=[RequesterSpec(
                       own_train=own_train, own_test=own_test,
                       neighborhood=neighborhood,
                       contributor_states=contributor_states,
                       battery=battery)],
                   cost_model=cost_model or CostModel(),
                   mobility=mobility, pooled_train=pooled_train, seed=seed)

    def fresh_requesters(self) -> List[RequesterSpec]:
        """Per-run copies of the mutable state, so every
        ``Experiment.run`` starts from the same world.  The engines
        mutate by REBINDING ``states[id]["params"]`` (refresh training)
        and replacing batteries — the param trees and data shards
        themselves are immutable arrays — so a two-level shallow copy of
        the state dicts is sufficient isolation without duplicating
        multi-MB training shards per run."""
        return [RequesterSpec(
            own_train=r.own_train, own_test=r.own_test,
            neighborhood=r.neighborhood,
            contributor_states={k: dict(v)
                                for k, v in r.contributor_states.items()},
            battery=copy.deepcopy(r.battery)) for r in self.requesters]

    def client_data(self, i: int = 0) -> List[tuple]:
        """The CFL/DFL client list seen from requester ``i``: its own
        shard first (client 0 = the requesting device), then each
        neighbor's shard in neighborhood order."""
        r = self.requesters[i]
        shards = [r.own_train]
        for dev in r.neighborhood:
            st = r.contributor_states.get(dev.device_id)
            if st is not None:
                shards.append(st["data"])
        return shards

    def pooled(self, i: int = 0) -> tuple:
        """The cloud-baseline corpus: ``pooled_train`` if given, else the
        concatenation of requester ``i``'s client shards."""
        if self.pooled_train is not None:
            return self.pooled_train
        shards = self.client_data(i)
        x = np.concatenate([np.asarray(s[0]) for s in shards])
        y = np.concatenate([np.asarray(s[1]) for s in shards])
        return x, y


@dataclasses.dataclass(frozen=True)
class MethodSpec:
    """Which method trains, and with which protocol knobs.

    The knobs are exactly :class:`repro.core.rounds.EnFedConfig`'s
    fields (world-owned ``seed``/``mobility`` excluded) so every
    registered method — EnFed and the re-plumbed baselines — consumes
    one configuration surface; ``topology`` only matters to ``"dfl"``.
    Coerce a bare registry name with :meth:`coerce`.
    """

    name: str = "enfed"
    desired_accuracy: float = 0.95       # A_A
    max_rounds: int = 10                 # R_A
    epochs: int = 5                      # E
    batch_size: int = 32                 # B_A
    n_max: int = 5                       # N_max
    battery_threshold: float = 0.2       # B_min
    offered_incentive: float = 0.6
    encrypt: bool = True
    contributor_refresh_epochs: int = 1
    strategy: Optional[AggregationStrategy] = None
    topology: str = "mesh"               # dfl: "mesh" | "ring"
    # transported-update compression (None | "int8" | "auto").  A
    # PROTOCOL knob, not an execution knob: it changes the simulated
    # outcome (wire bytes, eq. (4)-(7) energy, quantized params), so it
    # lives here and every method prices its transport through the same
    # repro.core.energy.update_wire_bytes helper.  "auto" resolves per
    # model size via repro.kernels.quantize.ops.resolve_compress — int8
    # only past the padding-overhead crossover, fp32 below it.
    compress: Optional[str] = None
    # unreliable-link world (None = perfect links).  Like ``compress``
    # this is a PROTOCOL knob: drops/retries/stale delivery change the
    # simulated outcome for enfed (Phase.DELIVER in both engines) and
    # re-price the extra transmissions for every method through the same
    # CostModel.retry_energy term.  Validation is FaultConfig's own
    # __post_init__ — a bad probability fails at spec construction.
    faults: Optional[FaultConfig] = None
    # asynchronous-cadence world (None = lockstep round barrier).  A
    # PROTOCOL knob like ``faults``: per-device speed classes, duty
    # cycles, transient offline windows and battery pacing desynchronize
    # the engines' round clocks (global event steps, straggler wire
    # images aggregated as-is) and price the idle windows through
    # CostModel.idle_energy.  enfed-only: the host-side baselines have
    # no per-device round clock — they warn-and-ignore, and the fleet
    # baselines refuse.  Validation is CadenceConfig's __post_init__.
    cadence: Optional[CadenceConfig] = None
    # Byzantine-contributor world (None = every contributor honest).  A
    # PROTOCOL knob like ``faults``/``cadence``: which links corrupt
    # their delivered wire image each round is counter-based world
    # state (repro.core.adversary), derived identically by both
    # engines.  enfed-only: the baselines' loop oracles define their
    # aggregation semantics without Phase.DELIVER — they warn-and-
    # ignore, and the fleet baselines refuse.
    adversary: Optional[AdversaryConfig] = None
    # Byzantine-robust Phase.AGGREGATE statistic ("none" | "clip" |
    # "trimmed_mean" | "median" — repro.kernels.robust), and the
    # staleness decay gamma on the aggregation weights (1.0 = none).
    # Both are enfed-only protocol knobs like ``adversary``.
    robust: str = "none"
    staleness_gamma: float = 1.0
    label: Optional[str] = None          # display/compare key (default: name)

    @property
    def key(self) -> str:
        """The name this run is reported/keyed under in a comparison —
        lets e.g. ``dfl``-mesh and ``dfl``-ring coexist in one table."""
        return self.label or self.name

    @classmethod
    def coerce(cls, m: Union[str, "MethodSpec"],
               like: Optional["MethodSpec"] = None) -> "MethodSpec":
        """``"dfl"`` -> a MethodSpec inheriting every knob from ``like``
        (or the defaults); a MethodSpec passes through unchanged.  The
        ``label`` is NOT inherited — it names ``like``'s own run, and
        carrying it over would mislabel the coerced method (and collide
        compare() keys)."""
        if isinstance(m, MethodSpec):
            return m
        base = like if like is not None else cls()
        return dataclasses.replace(base, name=str(m), label=None)

    def to_enfed_config(self, world: WorldSpec) -> EnFedConfig:
        """The method knobs + the world's seed/mobility as the config
        object both engines (and the re-plumbed baselines) execute."""
        return EnFedConfig(
            desired_accuracy=self.desired_accuracy,
            max_rounds=self.max_rounds,
            n_max=self.n_max,
            battery_threshold=self.battery_threshold,
            offered_incentive=self.offered_incentive,
            epochs=self.epochs,
            batch_size=self.batch_size,
            encrypt=self.encrypt,
            contributor_refresh_epochs=self.contributor_refresh_epochs,
            seed=world.seed,
            strategy=self.strategy,
            compress=self.compress,
            faults=self.faults,
            cadence=self.cadence,
            adversary=self.adversary,
            robust=self.robust,
            staleness_gamma=self.staleness_gamma,
            mobility=world.mobility)


@dataclasses.dataclass(frozen=True)
class ExecutionSpec:
    """How the run executes — never *what* it computes.

    ``engine="loop"`` is the readable Python oracle; ``"fleet"`` compiles
    all requesters into one jit program.  ``use_pallas`` / ``interpret``
    select the aggregation-kernel path (``interpret=None`` resolves per
    backend via ``repro.kernels.common.resolve_interpret``);
    ``round_chunk`` is the fleet engine's early-exit granularity.
    ``enfed``, ``dfl`` and ``cfl`` honor the engine choice — the
    baselines run as traced protocol variants of the same fleet program
    (``run_fleet(method=...)``), parity-tested against their loop
    learners.  ``cloud`` has no round structure to compile and always
    records ``engine="loop"``.
    """

    engine: str = "loop"                 # "loop" | "fleet"
    use_pallas: bool = True
    interpret: Optional[bool] = None
    round_chunk: int = 4
    # crash-resumable round state (enfed only; baselines warn-and-ignore).
    # ``checkpoint_dir`` serializes the flat wire-format round state +
    # batteries + masks + round clocks via repro.checkpoint every
    # ``checkpoint_every`` rounds (0 = the engine default: every round
    # for the loop engine, every round_chunk for the fleet engine);
    # ``resume_from`` restores the latest checkpoint in a directory and
    # continues bit-identically.  Execution knobs: a resumed run
    # computes the same outcome an uninterrupted one does.
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 0
    resume_from: Optional[str] = None
    # observability (repro.telemetry.TraceConfig): which artifacts the
    # run exports (event JSONL, Chrome trace, jax profiler dump, HLO cost
    # summary).  The strictest execution knob of all — observation can
    # never change the simulated outcome; tracing on is bitwise identical
    # to tracing off (enforced by tests/test_telemetry.py and the bench
    # trace smoke gate).  The fleet-only selection (hlo_stats)
    # warns-and-ignores on the loop engine.
    trace: Optional[object] = None

    def __post_init__(self):
        if self.engine not in ("loop", "fleet"):
            raise ValueError(f"unknown engine {self.engine!r} (loop|fleet)")
        if self.round_chunk < 1:
            raise ValueError(
                f"round_chunk must be >= 1 (got {self.round_chunk})")
        if self.checkpoint_every < 0:
            raise ValueError(
                f"checkpoint_every must be >= 0 (got {self.checkpoint_every})")
        if self.trace is not None:
            from repro.telemetry import TraceConfig

            if not isinstance(self.trace, TraceConfig):
                raise ValueError(
                    f"trace must be a repro.telemetry.TraceConfig "
                    f"(got {type(self.trace).__name__})")
