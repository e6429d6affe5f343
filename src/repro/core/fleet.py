"""Jit-native EnFed fleet engine: many concurrent requester sessions,
one compiled program, allocation- and transfer-lean.

The loop engine (``repro.core.rounds.EnFedSession``) executes Algorithm 1
as Python control flow — one ``task.fit`` dispatch per contributor per
round — which caps simulations at a handful of sessions.  This module
ports the same protocol onto stacked arrays so an entire fleet of
requesting devices advances together.  Design rules for the hot path at
R=512 and beyond:

* **Flat-parameter round state.**  Contributor params are raveled ONCE
  at setup (``repro.utils.tree.tree_ravel``) into a single (R, N, P)
  fp32 buffer — R requester sessions, N contributor slots, P flat model
  parameters.  That buffer IS the round state: the batched Pallas
  ``fedavg`` kernel (eq. 14 for every session, one launch) reads it
  directly, masked freezes are plain ``jnp.where`` on it, and it is
  donated to XLA (``donate_argnames``) so the round loop updates it in
  place.  Pytrees reappear only inside the per-device ``fit_one`` /
  ``eval_one`` views (``tree_unravel`` on a lane's (P,) slice) and at
  the host boundary when results are unpacked.

* **On-device minibatch scheduling.**  No index tensors are staged:
  batches come from the counter-based derived schedule
  (``repro.core.schedule``), evaluated inside the compiled round loop
  from the traced round number.  The loop engine's ``SupervisedTask.fit``
  evaluates the SAME derivation host-side, so both engines see identical
  batches by construction; prefix-stable per-sample scores make one
  traced program serve requesters with different shard sizes, including
  shards smaller than one batch (single padded step, zero-weight
  padding).

* **Compressed round state (``cfg.compress="int8"``).**  The round
  state is the transported thing, so when the protocol compresses the
  wire it must compress the state: under the knob the (R, N, P) fp32
  buffer is carried instead as a tile-padded int8 payload (R, N, Lp)
  plus per-tile fp32 scales — ~4x less staged host->device traffic and
  ~4x less device-resident round state
  (``FleetResult.device_round_state_bytes``).  Aggregation runs the
  fused dequant->fedavg kernel (``fedavg_flat_batched_q8``) straight on
  the wire-format buffer (the dequantized fp32 block never
  materializes); Phase.REFRESH dequantizes per-lane views for training
  and requantizes the result back into the buffer
  (``quantize_flat_batched``) in the same launch discipline.  fp32
  reappears only in per-lane views and the requester's own params.  The
  loop engine quantizes at the identical protocol points
  (``EnFedSession._wire_pack``), so the knob keeps full two-engine
  parity: bitwise on membership masks, allclose (tile-scale bound) on
  params — see tests/test_compress.py.

* **Deduplicated contributor shards, never re-densified.**  Requesters
  sharing one contributor population used to re-stage the same training
  shards R times as a dense (R, N, n_c, F) block — the dominant
  host->device transfer at R=512.  Shards are now staged once into a
  unique-shard table (U, n_c, F) plus an (R, N) gather index; and the
  program must NEVER undo that dedup in device memory: Phase.REFRESH
  gathers each lane's minibatch straight from the table inside the fit
  scan ((R·N, B, F) per step) instead of materializing the lane-dense
  (R·N, n_c, F) block up front.  ``FleetResult.staged_shard_bytes`` vs
  ``staged_shard_bytes_dense`` records the staging win,
  ``refresh_gather_bytes`` vs ``refresh_gather_bytes_dense`` the
  device-memory one.

* **Early-exit rounds, no dead work.**  The round loop is a chunked
  ``lax.while_loop``: after every ``round_chunk`` rounds the program
  checks whether any lane is still active and stops outright when the
  whole fleet is done, so a fleet that converges by round k executes
  O(k) round bodies, not ``max_rounds``.  Inside a chunk, each round
  body sits under ``lax.cond`` — once every lane has stopped (or the
  chunk runs past ``max_rounds``) the fit/refresh compute is skipped,
  not computed-and-discarded.  Because traces are preallocated
  (max_rounds, ...) buffers written in place, early exit leaves the
  untouched tail at zero — ``history["round_executed"]`` records exactly
  which round bodies ran.

* **Opportunistic world (``cfg.mobility``).**  With a
  ``repro.core.mobility.MobilityConfig`` set, the contract set is no
  longer frozen at handshake: contributor lanes hold the whole agreeing
  *candidate pool*, and every round body re-negotiates membership on
  device — counter-based waypoint positions from the traced round
  number, radio-range proximity, battery-floor releases (contributor
  batteries are traced (R, N) state discharged per participating
  round), and top-``n_max``-by-utility signing so arrivals undercut
  weaker members.  The resulting (R, N) membership mask IS the fedavg
  weight vector of that round's batched kernel launch (via
  ``topology.dynamic_round_weights``), gates Phase.REFRESH to current
  members, and indexes a per-member-count energy table for the
  requester's battery discharge.  ``history["member"]`` traces the mask
  per round.  The loop engine's ``EnFedSession._run_mobility`` derives
  the same world through the same ``repro.core.mobility`` functions with
  concrete round numbers — identical trajectories, masks, params, and
  battery curves by construction.

* **Method as a traced protocol variant.**  ``run_fleet(...,
  method="dfl"|"cfl")`` runs the paper's baselines as lanes of the SAME
  jit program (``_fleet_program``'s ``method`` is a static argument):
  the flat (R, N, P) round state now holds per-client node params, the
  batched Pallas fedavg kernel performs the aggregation step — gossip
  mixing rows for dfl (one launch per mixing-matrix row), the
  server-side data-size-weighted FedAvg for cfl — and the chunked
  ``lax.while_loop`` gives the baselines the same early exit enfed has.
  Which protocol steps trace is decided by the per-method phase mask
  (``protocol.method_phases``): baselines drop RENEGOTIATE / REFRESH /
  battery accounting, and AGGREGATE moves from requester-side to the
  client mixing/server step.  The loop learners
  (``repro.core.federated.CFLLearner`` / ``DFLLearner.run_config``) are
  the parity oracles — same per-client seeds (``seed + 31r + j`` cfl,
  ``seed + 77r + j`` dfl), same mixing matrices
  (``topology.group_mixing_matrix``), same stopping — so
  ``Experiment.compare`` at R=512 measures every method from one
  compiled program instead of extrapolating Python-loop sessions.

Phase mapping (vocabulary in ``repro.core.protocol``): handshake stays
host-side (cheap, deterministic numpy) and emits either the static
(R, N) contract mask + per-round aggregation weights, or — under
mobility — the candidate pool whose per-round RENEGOTIATE step runs on
device; collect+aggregate is the batched fedavg launch on the flat
buffer; fit/score/account are vmapped masked lanes; refresh trains
contributors on their own shards between rounds.

Parity with the loop engine — same aggregated params, round counts, stop
reasons, membership masks, and battery trajectories — is asserted by
``tests/test_fleet_engine.py`` across aggregation strategies, encrypt
on/off, and churn scenarios.  The AES-128-CTR transport is bit-exact
(validated in the loop engine / kernel tests), so the fleet engine
models encryption in the cost domain (byte counts -> eq. (4)-(7) ->
battery) without re-running the cipher per round.  All sessions share
one ``SupervisedTask``.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
from typing import Dict, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import adversary as adversary_mod
from repro.core import cadence as cadence_mod
from repro.core import faults as faults_mod
from repro.core import mobility as mobility_mod
from repro.core import protocol, schedule, topology
from repro.core.battery import BatteryState, discharge_level, load_efficiency
from repro.core.energy import CostModel, update_wire_bytes
from repro.core.incentive import (NeighborDevice, candidate_pool,
                                  sign_contracts_fleet)
from repro.core.rounds import EnFedConfig, SessionResult
from repro.kernels.fedavg.ops import (fedavg_flat_batched,
                                      fedavg_flat_batched_q8)
from repro.kernels.robust.ops import robust_aggregate, robust_aggregate_q8
from repro.kernels.quantize.ops import (dequantize_flat_batched, padded_len,
                                        quantize_flat_batched,
                                        resolve_compress)
from repro.models.classifiers import masked_cross_entropy_loss
from repro.optim import apply_updates
from repro.telemetry.profile import jit_hlo_stats
from repro.telemetry.spans import Timeline
from repro.utils.tree import (tree_bytes, tree_ravel, tree_size, tree_unravel,
                              tree_where)


@dataclasses.dataclass
class RequesterSpec:
    """One requesting device's inputs, mirroring ``EnFedSession``'s."""

    own_train: tuple                      # (x, y) numpy/array shard
    own_test: tuple
    neighborhood: Sequence[NeighborDevice]
    contributor_states: Dict[int, dict]   # device_id -> {params, data}
    battery: Optional[BatteryState] = None


@dataclasses.dataclass
class FleetResult:
    """Stacked outcome of one fleet program plus per-session views."""

    sessions: List[SessionResult]
    rounds: np.ndarray          # (R,) executed rounds per session
    stop_codes: np.ndarray      # (R,) protocol.STOP_* codes
    accuracy: np.ndarray        # (R,) final accuracy
    battery_level: np.ndarray   # (R,) final battery fraction
    total_energy_j: float       # summed eq. (5) energy across the fleet
    history: Dict[str, np.ndarray]  # (max_rounds, R) traces; "round_executed"
                                    # is (max_rounds,) — 1 where a round body
                                    # ran; "member" is (max_rounds, R, N)
                                    # under mobility (token zeros otherwise:
                                    # the static mask is just round_w > 0)
    staged_host_bytes: int = 0  # host->device bytes staged for the program
    staged_index_bytes: int = 0  # subset that is minibatch-schedule metadata
    staged_shard_bytes: int = 0  # contributor-shard table + gather indices
    staged_shard_bytes_dense: int = 0  # what the dense (R, N, ...) form costs
    staged_param_bytes: int = 0  # contributor-param round state as staged
                                 # (fp32 (R,N,P), or int8 payload + scales)
    device_round_state_bytes: int = 0  # device-RESIDENT round state carried
                                       # through the while_loop (fp32 vs int8)
    refresh_gather_bytes: int = 0  # per-step refresh minibatch gather
                                   # footprint ((R*N, B) rows from the table)
    refresh_gather_bytes_dense: int = 0  # the old re-densified (R*N, n_c, F)
                                         # block the gather replaces
    timeline: Optional[Timeline] = None  # host-side wall-clock spans
                                         # (stage/program/checkpoint/unpack)
    hlo_stats: Optional[dict] = None     # compiled-program flops/bytes
                                         # (TraceConfig.hlo_stats only)

    @property
    def history_raw(self) -> Dict[str, np.ndarray]:
        """Alias for ``history`` — fleet-level traces are not deprecated,
        but the alias keeps call sites uniform with SessionResult/
        RunResult, whose raw access goes through ``history_raw``."""
        return self.history


def _pad_stack(arrays, pad_len: int):
    """Stack ragged leading-axis arrays into (R, pad_len, ...) + mask."""
    shape = arrays[0].shape[1:]
    out = np.zeros((len(arrays), pad_len) + shape, arrays[0].dtype)
    mask = np.zeros((len(arrays), pad_len), np.float32)
    for i, a in enumerate(arrays):
        out[i, :len(a)] = a
        mask[i, :len(a)] = 1.0
    return out, mask


def _stack_trees(trees, template=None):
    """List of pytrees -> pytree with leading stacked axis (None entries
    become zeros_like(template))."""
    template = template if template is not None else next(t for t in trees if t is not None)
    filled = [t if t is not None else jax.tree_util.tree_map(np.zeros_like, template)
              for t in trees]
    return jax.tree_util.tree_map(lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]),
                                  *filled)


class FleetCarry(NamedTuple):
    """The fleet loop carry, by name.

    A ``typing.NamedTuple`` is a registered JAX pytree, so it rides
    ``lax.while_loop`` / ``fori_loop`` / donation unchanged — and the
    checkpoint path (``repro.checkpoint`` flattens with key paths)
    serializes each field under its NAME (``state/.contrib`` ...), so a
    restored ``.npz`` stays dtype-strict AND self-describing.  Token
    ``(1, ...)`` buffers stand in for state a variant doesn't carry.

    The per-lane cadence clock fields at the tail are what the
    asynchronous fleet adds: ``clock`` is each requester lane's OWN
    round number (advanced only on its cadence ticks), ``idle`` the
    event steps it has idled since its last executed round, and
    ``clock_h``/``idle_h`` the per-executed-round traces of both
    (which global step each round ran at / how long the lane waited
    for it) — token buffers in lockstep runs.
    """

    contrib: jnp.ndarray      # (R, N, P|Lp) flat round state (int8 wire
                              # payload under compress)
    cscale: jnp.ndarray       # (R, N, T) per-tile scales | token
    live: jnp.ndarray         # (V, P|Lp) dedup'd refresh rows | token
    live_s: jnp.ndarray       # (V, T) their scales | token
    last: jnp.ndarray         # (R, P) requester params
    level: jnp.ndarray        # (R,) requester battery fraction
    active: jnp.ndarray       # (R,) bool — BOTH programs' stop poll
    stop_code: jnp.ndarray    # (R,) protocol.STOP_* codes
    rounds_done: jnp.ndarray  # (R,) executed rounds per lane
    clevel: jnp.ndarray       # (R, N) contributor batteries | token
    acc_h: jnp.ndarray        # (max_rounds, R) accuracy trace
    loss_h: jnp.ndarray       # (max_rounds, R) loss trace
    bat_h: jnp.ndarray        # (max_rounds, R) battery trace
    exec_h: jnp.ndarray       # (max_rounds, R) executed-lane trace
    body_h: jnp.ndarray       # (max_events,) round-body-ran trace
    member_h: jnp.ndarray     # (max_rounds, R, N) membership | token
    prev: jnp.ndarray         # (R, N, P|Lp) stale-delivery wire
                              # snapshot | token
    prev_s: jnp.ndarray       # (R, N, T) its scales | token
    drop_h: jnp.ndarray       # (max_rounds, R) fault drops | token
    retry_h: jnp.ndarray      # (max_rounds, R) fault retries | token
    stale_h: jnp.ndarray      # (max_rounds, R) stale deliveries | token
    deliver_h: jnp.ndarray    # (max_rounds, R, N) deliver mask | token
    clock: jnp.ndarray        # (R,) int32 per-lane round clock | token
    idle: jnp.ndarray         # (R,) int32 idle steps since the lane's
                              # last executed round | token
    clock_h: jnp.ndarray      # (max_rounds, R) int32 global step each
                              # round executed at | token
    idle_h: jnp.ndarray       # (max_rounds, R) int32 idle-steps-before
                              # trace | token
    corrupt_h: jnp.ndarray    # (max_rounds, R, N) corrupted-delivery
                              # mask (adversary worlds) | token
    clip_h: jnp.ndarray       # (max_rounds, R, N) norm-clipped mask
                              # (robust != "none") | token


def _phase_scope(phase: protocol.Phase):
    """``jax.named_scope`` of one protocol phase: the ops traced inside
    carry the phase's name in their HLO ``op_name`` metadata, which
    :func:`repro.telemetry.profile.hlo_phases` maps back to phases."""
    return jax.named_scope(phase.value)


def _make_round_fn(task, use_pallas, interpret, do_refresh, max_rounds,
                   max_events, epochs, batch, steps_max, ref_epochs,
                   ref_steps, spec, mob, n_max, strategy, compress, n_params,
                   method, fc, cc, ac, robust, gamma, n_req, n_lanes, arrays):
    """Build the traced per-round body shared by BOTH fleet programs.

    :func:`_fleet_program` (the compiled chunked ``while_loop``) and
    :func:`_fleet_chunk_program` (one chunk per call, for host-driven
    checkpoint/resume) trace the SAME ``maybe_round`` returned here, so
    the two execution paths cannot drift apart — which is what makes
    killed-at-round-k-and-resumed bit-identical to uninterrupted.

    ``fc`` is the static :class:`repro.core.faults.FaultConfig` (None =
    perfect links); under faults every round derives the per-link
    (delivered, attempts, stale) outcomes from the counter-based fault
    world (``Phase.DELIVER``), masks undelivered links out of the fedavg
    weights, aggregates round-(r-1) wire images for stale links (the
    ``prev`` carry), and re-prices every extra receive window through
    the staged ``e_retry`` term.

    ``cc`` is the static :class:`repro.core.cadence.CadenceConfig` (None
    = lockstep).  Under cadence ``maybe_round`` iterates GLOBAL EVENT
    STEPS, not rounds: world state (mobility kinematics, fault weather)
    is keyed on the step counter ``rr``, while each requester lane
    carries its own round ``clock`` that advances only on the lane's
    cadence ticks — a step where no lane ticks costs one idle increment
    and no compute (``lax.cond``, the early-exit skip machinery), and a
    lane that doesn't tick while others execute keeps its wire image
    resident for them to aggregate as-is (the straggler path).  With
    ``cc=None``, ``max_events == max_rounds`` and every lane ticks every
    step, so the traced program is today's lockstep loop bit for bit.

    ``ac`` is the static :class:`repro.core.adversary.AdversaryConfig`
    (None = honest world): per-link corruption outcomes derive from the
    same counter-based fold_in discipline as faults/cadence, keyed on
    the event step, and corrupt the WIRE image at the transport point —
    after the stale-delivery substitution, per the Phase.DELIVER
    ordering pin in ``repro.core.protocol``.  ``robust`` selects the
    Phase.AGGREGATE statistic (``repro.kernels.robust``) and ``gamma``
    the staleness decay on the aggregation weights
    (``protocol.decayed_round_weights``); both default to the plain
    fedavg path bit for bit.
    """
    model, opt = task.model, task._opt
    R, N = n_req, n_lanes
    P = n_params
    phases = protocol.method_phases(method)
    if method == "enfed":
        n_pad = arrays["own_x"].shape[1]
    mobility_on = (mob is not None) and (protocol.Phase.RENEGOTIATE in phases)
    faults_on = (fc is not None) and (protocol.Phase.DELIVER in phases)
    compress_on = compress == "int8"
    cadence_on = cc is not None
    adversary_on = (ac is not None) and (protocol.Phase.DELIVER in phases)
    robust_on = robust != "none"
    decay_on = float(gamma) != 1.0

    def _fit_lane(flat_p, get_xy, idx, w):
        """Identical math to SupervisedTask.fit for one device's shard,
        on a flat (P,) parameter view; ``get_xy`` maps a (B,) index row
        to that step's minibatch (direct shard slice for requesters,
        unique-table gather for contributor refresh)."""
        E, S, B = idx.shape
        params = tree_unravel(spec, flat_p)

        def one_step(carry, sv):
            p, s = carry
            ib, wb = sv
            xb, yb = get_xy(ib)
            loss, grads = jax.value_and_grad(
                lambda pp: masked_cross_entropy_loss(
                    model.forward(pp, xb), yb, wb))(p)
            upd, s2 = opt.update(grads, s, p)
            p2 = apply_updates(p, upd)
            take = jnp.sum(wb) > 0
            return ((tree_where(take, p2, p), tree_where(take, s2, s)),
                    jnp.where(take, loss, 0.0))

        (params, _), losses = jax.lax.scan(
            one_step, (params, opt.init(params)),
            (idx.reshape(E * S, B), w.reshape(E * S, B)))
        valid_steps = (w.sum(-1) > 0).astype(jnp.float32).reshape(E, S).sum(1)
        per_epoch = losses.reshape(E, S).sum(1) / jnp.maximum(valid_steps, 1.0)
        flat_out, _ = tree_ravel(params)
        return flat_out, per_epoch[-1]

    def fit_one(flat_p, x, y, idx, w):
        return _fit_lane(flat_p, lambda ib: (x[ib], y[ib]), idx, w)

    def eval_one(flat_p, x, y, mask):
        logits = model.forward(tree_unravel(spec, flat_p), x)
        correct = (jnp.argmax(logits, axis=-1) == y).astype(jnp.float32)
        return jnp.sum(correct * mask) / jnp.maximum(jnp.sum(mask), 1.0)

    # Static worlds dedup the refresh COMPUTE itself: every active lane
    # subscribed to the same (device, shard, params) follows the
    # identical refresh trajectory (refresh is the only mutation of a
    # contributor lane, and it hits exactly the active lanes each
    # round), so one "live" row per unique subscription is trained and
    # scattered to lanes.  Under mobility membership gaps make lanes
    # diverge (a lane skips refresh in non-member rounds), so the
    # per-lane path remains — and cadence gaps (a contributor that
    # doesn't tick skips its refresh) desynchronize lanes the same way.
    refresh_dedup = do_refresh and not mobility_on and not cadence_on
    if do_refresh:
        # Phase.REFRESH schedule is round-invariant (seed = cfg.seed +
        # device_id), so its indices are derived once per program, on
        # device, and reused every round.  Training minibatches come
        # straight from the deduplicated unique-shard table, gathered
        # per step INSIDE the fit scan — the dedup is never undone into
        # an (R*N, n_c, F) lane-dense block in device memory.
        with _phase_scope(protocol.Phase.REFRESH):
            nc_pad = arrays["cx_tab"].shape[1]
            if refresh_dedup:
                ref_scores = jax.vmap(
                    lambda s: schedule.epoch_scores(s, ref_epochs, nc_pad))(
                    arrays["u_seed"])
                ref_idx, ref_w = jax.vmap(
                    lambda sc, n: schedule.plan_from_scores(sc, n, batch,
                                                            ref_steps))(
                    ref_scores, arrays["u_n"])
                ref_rows = arrays["u_cidx"]
                uidx_flat = arrays["ref_uidx"].reshape(R * N)
                # padded contributor slots subscribe to no live row; their
                # old no-op-refresh contents must survive the scatter
                lane_valid = arrays["lane_valid"].reshape(R * N, 1)
            else:
                ref_scores = jax.vmap(jax.vmap(
                    lambda s: schedule.epoch_scores(s, ref_epochs, nc_pad)))(
                    arrays["ref_seeds"])
                ref_idx, ref_w = jax.vmap(jax.vmap(
                    lambda sc, n: schedule.plan_from_scores(sc, n, batch,
                                                            ref_steps)))(
                    ref_scores, arrays["ref_n"])
                ref_rows = arrays["cidx"].reshape(R * N)
                ref_idx = ref_idx.reshape(R * N, ref_epochs, ref_steps, batch)
                ref_w = ref_w.reshape(R * N, ref_epochs, ref_steps, batch)

        def fit_refresh(flat_p, u, idx, w):
            """One refresh row: minibatch (B, F) rows are gathered from
            the shard table by (row u, index ib)."""
            return _fit_lane(
                flat_p,
                lambda ib: (arrays["cx_tab"][u, ib], arrays["cy_tab"][u, ib]),
                idx, w)

    def run_round(state, rr, tick=None):
        """One live round body.  Entered only via lax.cond when at least
        one lane is active and rr < max_events (so ``active`` needs no
        extra validity masking inside).  Under cadence ``tick`` is the
        (R,) bool of lanes executing THIS event step (already masked by
        ``active``); lockstep passes None and every active lane
        executes."""
        (contrib, cscale, live, live_s, last, level, active, stop_code,
         rounds_done, clevel, acc_h, loss_h, bat_h, exec_h, body_h,
         member_h, prev, prev_s, drop_h, retry_h, stale_h, deliver_h,
         clock, idle, clock_h, idle_h, corrupt_h, clip_h) = state
        # which lanes execute a protocol round at this event step; under
        # cadence the rest idle in place (their whole ACCOUNT/history
        # update is masked out below)
        exec_mask = tick if cadence_on else active
        if cadence_on:
            # contributor ticks gate Phase.REFRESH only — a straggler's
            # wire image stays resident and is aggregated as-is by the
            # lanes that did tick
            ctick = cadence_mod.tick_mask(cc, rr, arrays["cad_cand_ids"])
            # each executing lane writes history at ITS OWN round row
            row = jnp.clip(clock, 0, max_rounds - 1)
            lanes = jnp.arange(R)

            def put_lane(buf, vals):
                cur = buf[row, lanes]
                if vals.ndim == 2:      # (R, N) membership-shaped rows
                    return buf.at[row, lanes].set(
                        jnp.where(exec_mask[:, None], vals, cur))
                return buf.at[row, lanes].set(
                    jnp.where(exec_mask, vals, cur))

        # Phase.RENEGOTIATE (mobility): release members that walked out
        # of radio range or hit the battery floor, sign in-range
        # arrivals, let higher-utility arrivals displace weaker members
        # — all on device, from the traced round number.  Under faults,
        # streak-blocked links lose eligibility here too.
        with _phase_scope(protocol.Phase.RENEGOTIATE):
            if mobility_on:
                blocked = (faults_mod.blocked_mask(
                    fc, rr, arrays["freq_ids"], arrays["cand_ids"])
                    if faults_on else None)
                member, rank, _util = mobility_mod.membership_step(
                    mob, rr, arrays["req_ids"], arrays["cand_ids"],
                    arrays["cand_mask"], arrays["base_util"], clevel, n_max,
                    blocked=blocked)
                round_w = topology.dynamic_round_weights(member, rank,
                                                         strategy)
                count = jnp.sum(member, axis=1).astype(jnp.int32)
            else:
                round_w = arrays["round_w"]

        # Phase.DELIVER (faults): which attempting links actually landed
        # an update this round, how many transmissions each burned, and
        # which delivered the round-(r-1) wire image instead.  The
        # delivered mask multiplies straight into the fedavg weights —
        # the kernel's normalized masked mean IS the graceful
        # degradation.
        with _phase_scope(protocol.Phase.DELIVER):
            if faults_on:
                delivered, attempts, stale = faults_mod.link_outcomes(
                    fc, rr, arrays["freq_ids"], arrays["fcand_ids"])
                if mobility_on:
                    att_mask = member           # members attempt; blocked
                    #   links were already released at RENEGOTIATE
                else:
                    att_mask = arrays["fsigned"] & ~faults_mod.blocked_mask(
                        fc, rr, arrays["freq_ids"], arrays["fcand_ids"])
                delivered = delivered & att_mask
                dcount = jnp.sum(delivered, axis=1).astype(jnp.int32)
                round_w = round_w * delivered.astype(round_w.dtype)
                drops_r = jnp.sum(att_mask & ~delivered, axis=1).astype(
                    jnp.float32)
                retries_r = jnp.sum(jnp.where(att_mask, attempts - 1, 0),
                                    axis=1).astype(jnp.float32)
                stale_r = jnp.sum(delivered & stale,
                                  axis=1).astype(jnp.float32)
                stale_sel = (delivered & stale)[:, :, None]

        # Phase.COLLECT + Phase.AGGREGATE: one batched kernel launch,
        # directly on the flat round state; under mobility the
        # membership mask IS the kernel's weight vector, and a lane whose
        # whole neighborhood churned away keeps training on its own
        # previous params.  Compressed state runs the fused
        # dequant->fedavg kernel on the wire-format buffer (the padding
        # tail dequantizes to zero and is sliced off).  Stale links
        # substitute the second wire-format-resident buffer (``prev``) —
        # the fp32 image never materializes either way.
        with _phase_scope(protocol.Phase.AGGREGATE):
            src = jnp.where(stale_sel, prev, contrib) if faults_on else contrib
            if compress_on:
                src_s = (jnp.where(stale_sel, prev_s, cscale) if faults_on
                         else cscale)
            if adversary_on:
                # Byzantine corruption at the transport point: AFTER the
                # stale substitution (ordering pin, protocol.Phase.DELIVER),
                # keyed on the delivering event step, applied to the wire
                # image itself (int8 codes/scales under compress — never
                # re-densified).  The mask derivation is the shared
                # counter-based closed form, so the loop oracle's per-link
                # draws match bit for bit.
                cmask = adversary_mod.corruption_mask(
                    ac, rr, arrays["areq_ids"], arrays["acand_ids"])
                if compress_on:
                    src, src_s = adversary_mod.corrupt_wire_batched(
                        ac, src, src_s, cmask, rr, arrays["areq_ids"],
                        arrays["acand_ids"])
                    # the quantization padding tail is not part of the model
                    # update: the loop oracle's dense view slices to P before
                    # any robust statistic, so a noise payload's tail codes
                    # must not leak into the fused q8 clip norms.  Honest
                    # tails are already exact zero codes — this multiply is
                    # the identity for them.
                    if P < src.shape[-1]:
                        src = src * (jnp.arange(src.shape[-1])
                                     < P).astype(src.dtype)
                else:
                    src = adversary_mod.corrupt_dense_batched(
                        ac, src, cmask, rr, arrays["areq_ids"],
                        arrays["acand_ids"])
            if decay_on:
                # staleness-decayed weights (gamma**lag): the stride lag of
                # each resident image under cadence, +1 for a fault-stale
                # delivery — closed form, no new carried state; masks are
                # exact 0/1 factors so applying decay after them is bitwise
                # identical to the loop engine's decay-then-mask order
                lag = (cadence_mod.image_lag(cc, rr, arrays["cad_cand_ids"])
                       if cadence_on else jnp.zeros((R, N), jnp.int32))
                if faults_on:
                    lag = lag + (delivered & stale).astype(jnp.int32)
                round_w = protocol.decayed_round_weights(round_w, lag, gamma)
            if robust_on:
                # Phase.AGGREGATE hardened: the robust statistic runs on the
                # SAME masked lane buffer the fedavg kernel would see —
                # both engines call the one repro.kernels.robust entry, so
                # the clipped masks are bitwise identical by construction
                if compress_on:
                    glob, clipped = robust_aggregate_q8(
                        src, src_s, round_w, method=robust,
                        use_pallas=use_pallas, interpret=interpret)
                    glob = glob[:, :P]
                else:
                    glob, clipped = robust_aggregate(
                        src, round_w, method=robust,
                        use_pallas=use_pallas, interpret=interpret)
            elif compress_on:
                glob = fedavg_flat_batched_q8(
                    src, src_s, round_w,
                    use_pallas=use_pallas, interpret=interpret)[:, :P]
            else:
                glob = fedavg_flat_batched(src, round_w,
                                           use_pallas=use_pallas,
                                           interpret=interpret)
            if adversary_on:
                # the delivered-and-corrupted trace row: a corruption draw
                # only counts when that link actually fed eq. (14)'s buffer
                agg_mask = (delivered if faults_on
                            else (member if mobility_on
                                  else arrays["asigned"]))
                corrupted_r = cmask & agg_mask
            if mobility_on or faults_on:
                # nothing fed eq. (14) this round: fall back to own params,
                # exactly like the loop engine's empty-neighborhood case
                fed_count = dcount if faults_on else count
                glob = jnp.where((fed_count > 0)[:, None], glob, last)

        # Phase.FIT (requesters personalize) + Phase.SCORE.  The round's
        # minibatch indices are derived here, on device, from the traced
        # round number — nothing was staged from the host.  Under
        # cadence the fit seed is the LANE'S OWN round clock, not the
        # global step, so a straggler lane draws the same minibatches
        # the loop oracle draws for its r-th round.
        with _phase_scope(protocol.Phase.FIT):
            if cadence_on:
                lane_scores = jax.vmap(
                    lambda c: schedule.epoch_scores(arrays["seed0"] + c,
                                                    epochs, n_pad))(clock)
                idx, w = jax.vmap(
                    lambda sc, n: schedule.plan_from_scores(sc, n, batch,
                                                            steps_max))(
                    lane_scores, arrays["n_own"])
            else:
                scores = schedule.epoch_scores(arrays["seed0"] + rr, epochs,
                                               n_pad)
                idx, w = jax.vmap(
                    lambda n: schedule.plan_from_scores(scores, n, batch,
                                                        steps_max))(
                    arrays["n_own"])
            new_flat, last_loss = jax.vmap(fit_one)(
                glob, arrays["own_x"], arrays["own_y"], idx, w)
        with _phase_scope(protocol.Phase.SCORE):
            acc = jax.vmap(eval_one)(new_flat, arrays["test_x"],
                                     arrays["test_y"], arrays["test_mask"])

        # Phase.ACCOUNT: traced battery discharge for executed rounds;
        # under mobility (or faults) the round energy depends on how many
        # updates actually fed eq. (14) — a host-precomputed per-count
        # table, gathered with the traced count — and every fault-world
        # drop or retry burns one MORE receive window (``e_retry``).
        with _phase_scope(protocol.Phase.ACCOUNT):
            if mobility_on or faults_on:
                e_round = jnp.take_along_axis(
                    arrays["e_tab"],
                    (dcount if faults_on else count)[:, None], axis=1)[:, 0]
            else:
                e_round = arrays["e_round"]
            if faults_on:
                e_round = e_round + (drops_r + retries_r) * arrays["e_retry"]
            level_new = discharge_level(level, e_round,
                                        arrays["capacity"], arrays["eff"])
            reached = acc >= arrays["desired_accuracy"]
            low = level_new < arrays["battery_threshold"]
            if cadence_on:
                # only executing lanes pay the round, advance their clocks,
                # or may stop; ``cont`` (survives the round) still gates the
                # final-round refresh even when the clock hits the budget —
                # matching the loop oracle, whose last executed round still
                # refreshes before the budget break
                stop_code = jnp.where(exec_mask & reached,
                                      protocol.STOP_ACCURACY,
                                      jnp.where(exec_mask & ~reached & low,
                                                protocol.STOP_BATTERY,
                                                stop_code))
                level = jnp.where(exec_mask, level_new, level)
                rounds_done = rounds_done + exec_mask.astype(jnp.int32)
                last = jnp.where(exec_mask[:, None], new_flat, last)
                cont = active & ~(exec_mask & (reached | low))
                clock_new = clock + exec_mask.astype(jnp.int32)
                next_active = cont & (clock_new < max_rounds)
            else:
                stop_code = jnp.where(active & reached, protocol.STOP_ACCURACY,
                                      jnp.where(active & ~reached & low,
                                                protocol.STOP_BATTERY,
                                                stop_code))
                level = jnp.where(active, level_new, level)
                rounds_done = rounds_done + active.astype(jnp.int32)
                last = jnp.where(active[:, None], new_flat, last)
                cont = next_active = active & ~reached & ~low
                clock_new = clock

            # Contributor-side discharge (mobility): members paid the
            # transmission term this round — once per ATTEMPT under faults,
            # the sender's radio burns the same energy whether or not the
            # update lands; the refresh term only while their requester's
            # session survives.  Releases at the battery floor feed back
            # into the NEXT round's membership_step.
            if mobility_on:
                e_tx_round = (arrays["e_tx"] * attempts.astype(jnp.float32)
                              if faults_on else arrays["e_tx"])
                # under cadence only members of EXECUTING lanes paid a
                # transmission this step, and the refresh term additionally
                # requires the contributor's own tick
                refresh_on = (cont[:, None] & exec_mask[:, None] & ctick
                              if cadence_on else next_active[:, None])
                clevel = mobility_mod.contributor_discharge(
                    clevel, member & exec_mask[:, None], e_tx_round,
                    arrays["e_ref"], refresh_on,
                    mob.contributor_capacity_j)

        # the round-(r-1) image next round's stale links will deliver:
        # snapshot the PRE-refresh round state (what this round
        # aggregated), still wire-format resident; under cadence only
        # the lanes that executed re-snapshot — a straggler's "previous
        # round" stays whatever its own last round aggregated
        if faults_on:
            if cadence_on:
                prev = jnp.where(exec_mask[:, None, None], contrib, prev)
                if compress_on:
                    prev_s = jnp.where(exec_mask[:, None, None], cscale,
                                       prev_s)
            else:
                prev, prev_s = contrib, cscale

        # Phase.REFRESH: contributors keep training (frozen once their
        # requester stops; under mobility, only CURRENT members train);
        # skipped entirely — not computed-and-masked — when no lane
        # survives into the next round.  Under compress, each lane's
        # wire payload is dequantized into its fp32 training view and
        # the result requantized back — the round state never persists
        # at full precision.
        with _phase_scope(protocol.Phase.REFRESH):
            if do_refresh:
                if cadence_on:
                    # a contributor refreshes when its requester's lane
                    # executed AND survives AND the contributor itself
                    # ticked this step; signed-lane validity replaces the
                    # dedup path's lane_valid in static worlds
                    rmask = cont[:, None] & exec_mask[:, None] & ctick
                    rmask = rmask & (member if mobility_on
                                     else arrays["cad_signed"])
                else:
                    rmask = (next_active[:, None] & member) if mobility_on \
                        else next_active[:, None]

                def refresh(args):
                    lv, lvs, c, sc = args
                    # the training source: the live unique rows (dedup) or
                    # every lane (mobility); compressed state is dequantized
                    # into its fp32 training view here and requantized below
                    if refresh_dedup:
                        src = (dequantize_flat_batched(lv, lvs)[:, :P]
                               if compress_on else lv)
                    else:
                        src = (dequantize_flat_batched(
                            c.reshape(R * N, -1), sc.reshape(R * N, -1))[:, :P]
                            if compress_on else c.reshape(R * N, P))
                    refreshed, _ = jax.vmap(fit_refresh)(
                        src, ref_rows, ref_idx, ref_w)
                    take = jnp.broadcast_to(rmask, (R, N)).reshape(R * N, 1)
                    if refresh_dedup:
                        take = take & lane_valid
                    if compress_on:
                        lp = c.shape[-1]
                        q2, s2 = quantize_flat_batched(
                            jnp.pad(refreshed, ((0, 0), (0, lp - P))),
                            use_pallas=use_pallas, interpret=interpret)
                        q_lane = q2[uidx_flat] if refresh_dedup else q2
                        s_lane = s2[uidx_flat] if refresh_dedup else s2
                        return ((q2, s2) if refresh_dedup else (lv, lvs)) + (
                            jnp.where(take, q_lane, c.reshape(R * N, lp))
                            .reshape(c.shape),
                            jnp.where(take, s_lane, sc.reshape(R * N, -1))
                            .reshape(sc.shape))
                    p_lane = (refreshed[uidx_flat] if refresh_dedup
                              else refreshed)
                    return ((refreshed if refresh_dedup else lv), lvs,
                            jnp.where(take[..., None].reshape(R, N, 1),
                                      p_lane.reshape(R, N, P), c), sc)

                live, live_s, contrib, cscale = jax.lax.cond(
                    jnp.any(rmask) if cadence_on else jnp.any(next_active),
                    refresh, lambda a: a, (live, live_s, contrib, cscale))

        def put(buf, row):
            return jax.lax.dynamic_update_slice_in_dim(buf, row[None], rr, 0)

        if cadence_on:
            # each executing lane lands at its OWN round row (masked
            # scatter); the (max_events,) body trace still records this
            # global step's body running
            acc_h = put_lane(acc_h, acc)
            loss_h = put_lane(loss_h, last_loss)
            bat_h = put_lane(bat_h, level)
            exec_h = put_lane(exec_h, exec_mask.astype(jnp.float32))
            clock_h = put_lane(clock_h,
                               jnp.broadcast_to(jnp.asarray(rr, jnp.int32),
                                                (R,)))
            idle_h = put_lane(idle_h, idle)
            idle = jnp.where(exec_mask, 0,
                             idle + (active & ~exec_mask).astype(jnp.int32))
            clock = clock_new
            body_h = put(body_h, jnp.float32(1.0))
            if mobility_on:
                member_h = put_lane(
                    member_h,
                    (member & exec_mask[:, None]).astype(jnp.float32))
            if faults_on:
                af = exec_mask.astype(jnp.float32)
                drop_h = put_lane(drop_h, drops_r * af)
                retry_h = put_lane(retry_h, retries_r * af)
                stale_h = put_lane(stale_h, stale_r * af)
                deliver_h = put_lane(
                    deliver_h,
                    (delivered & exec_mask[:, None]).astype(jnp.float32))
            if adversary_on:
                corrupt_h = put_lane(
                    corrupt_h,
                    (corrupted_r & exec_mask[:, None]).astype(jnp.float32))
            if robust_on:
                clip_h = put_lane(
                    clip_h,
                    (clipped & exec_mask[:, None]).astype(jnp.float32))
        else:
            acc_h = put(acc_h, acc)
            loss_h = put(loss_h, last_loss)
            bat_h = put(bat_h, level)
            exec_h = put(exec_h, active.astype(jnp.float32))
            body_h = put(body_h, jnp.float32(1.0))
            if mobility_on:
                member_h = put(member_h,
                               (member & active[:, None]).astype(jnp.float32))
            if faults_on:
                af = active.astype(jnp.float32)
                drop_h = put(drop_h, drops_r * af)
                retry_h = put(retry_h, retries_r * af)
                stale_h = put(stale_h, stale_r * af)
                deliver_h = put(deliver_h,
                                (delivered
                                 & active[:, None]).astype(jnp.float32))
            if adversary_on:
                corrupt_h = put(
                    corrupt_h,
                    (corrupted_r & active[:, None]).astype(jnp.float32))
            if robust_on:
                clip_h = put(clip_h,
                             (clipped & active[:, None]).astype(jnp.float32))
        return FleetCarry(contrib, cscale, live, live_s, last, level,
                          next_active, stop_code, rounds_done, clevel, acc_h,
                          loss_h, bat_h, exec_h, body_h, member_h, prev,
                          prev_s, drop_h, retry_h, stale_h, deliver_h,
                          clock, idle, clock_h, idle_h, corrupt_h, clip_h)

    # ---- baseline method variants (dfl / cfl) ------------------------------
    # Same scaffolding — flat (R, N, P) state, batched fedavg kernels,
    # chunked early-exit while_loop — with the phase mask deciding what
    # traces: no RENEGOTIATE, no REFRESH, no battery term in ACCOUNT,
    # and AGGREGATE moves to the client side (dfl gossip mixing) or the
    # virtual server (cfl data-size FedAvg).  Lane j of requester i is
    # client j of the loop learners' client_data list (client 0 = the
    # requester's own shard), so seeds, schedules, mixing weights, and
    # stopping reproduce CFLLearner/DFLLearner.run_config exactly.
    if method in ("dfl", "cfl"):
        assert protocol.Phase.REFRESH not in phases
        nc_pad = arrays["cx_tab"].shape[1]
        seed_stride = 31 if method == "cfl" else 77
        cidx_flat = arrays["cidx"].reshape(R * N)
        cli_n_flat = arrays["cli_n"].reshape(R * N)
        lane_j = jnp.arange(R * N, dtype=jnp.int32) % N

        def fit_client(flat_p, u, idx, w):
            """One client lane: minibatches gathered straight from the
            deduplicated shard table (never re-densified)."""
            return _fit_lane(
                flat_p,
                lambda ib: (arrays["cx_tab"][u, ib], arrays["cy_tab"][u, ib]),
                idx, w)

        def run_round(state, rr, tick=None):
            (contrib, cscale, live, live_s, last, level, active, stop_code,
             rounds_done, clevel, acc_h, loss_h, bat_h, exec_h, body_h,
             member_h, prev, prev_s, drop_h, retry_h, stale_h, deliver_h,
             clock, idle, clock_h, idle_h, corrupt_h, clip_h) = state

            # Phase.FIT at every client lane.  The loop oracles seed each
            # client fit with cfg.seed + stride*r + client_index; the
            # prefix-stable derived schedule reproduces
            # SupervisedTask.fit's minibatches bit for bit, with padded
            # lanes (n=0) collapsing to zero-weight no-op steps.
            with _phase_scope(protocol.Phase.FIT):
                scores = jax.vmap(
                    lambda j: schedule.epoch_scores(
                        arrays["seed0"] + seed_stride * rr + j, epochs,
                        nc_pad))(
                    jnp.arange(N, dtype=jnp.int32))
                idx, w = jax.vmap(
                    lambda j, n: schedule.plan_from_scores(
                        scores[j], n, batch, steps_max))(lane_j, cli_n_flat)
                if method == "cfl":
                    # every client trains FROM THE SHARED GLOBAL (in `last`)
                    src = jnp.broadcast_to(last[:, None],
                                           (R, N, P)).reshape(R * N, P)
                else:
                    # dfl: every node trains from its own params
                    src = contrib.reshape(R * N, P)
                fitted, fit_loss = jax.vmap(fit_client)(src, cidx_flat, idx, w)
                fitted = fitted.reshape(R, N, P)

            # Phase.COLLECT + Phase.AGGREGATE on the flat round state:
            # cfl is one server-side data-size-weighted kernel launch;
            # dfl applies the row-stochastic mixing matrix as one launch
            # per output row (rows sum to 1, so the kernel's normalized
            # weighted mean IS the gossip mix of apply_mixing).
            with _phase_scope(protocol.Phase.AGGREGATE):
                if method == "cfl":
                    glob = fedavg_flat_batched(fitted, arrays["cli_w"],
                                               use_pallas=use_pallas,
                                               interpret=interpret)
                    new_contrib, new_last = fitted, glob
                else:
                    mixed = jnp.stack(
                        [fedavg_flat_batched(fitted, arrays["mix_w"][:, k, :],
                                             use_pallas=use_pallas,
                                             interpret=interpret)
                         for k in range(N)], axis=1)
                    new_contrib, new_last = mixed, mixed[:, 0]

            # Phase.SCORE: the loop oracles evaluate the aggregated
            # global (cfl) / node 0 after mixing (dfl) on requester_test
            with _phase_scope(protocol.Phase.SCORE):
                acc = jax.vmap(eval_one)(new_last, arrays["test_x"],
                                         arrays["test_y"], arrays["test_mask"])

            # Phase.ACCOUNT without the battery term: the baselines
            # carry no battery (energy is priced host-side per session
            # via cfl_session/dfl_session), so stopping is accuracy or
            # the round budget only.
            with _phase_scope(protocol.Phase.ACCOUNT):
                reached = acc >= arrays["desired_accuracy"]
                stop_code = jnp.where(active & reached, protocol.STOP_ACCURACY,
                                      stop_code)
                rounds_done = rounds_done + active.astype(jnp.int32)
                last = jnp.where(active[:, None], new_last, last)
                contrib = jnp.where(active[:, None, None], new_contrib,
                                    contrib)
                next_active = active & ~reached

            def put(buf, row):
                return jax.lax.dynamic_update_slice_in_dim(buf, row[None], rr, 0)

            acc_h = put(acc_h, acc)
            # requester-lane (client 0) last-epoch fit loss per round
            loss_h = put(loss_h, fit_loss.reshape(R, N)[:, 0])
            bat_h = put(bat_h, level)
            exec_h = put(exec_h, active.astype(jnp.float32))
            body_h = put(body_h, jnp.float32(1.0))
            return FleetCarry(contrib, cscale, live, live_s, last, level,
                              next_active, stop_code, rounds_done, clevel,
                              acc_h, loss_h, bat_h, exec_h, body_h, member_h,
                              prev, prev_s, drop_h, retry_h, stale_h,
                              deliver_h, clock, idle, clock_h, idle_h,
                              corrupt_h, clip_h)

    def maybe_round(i, carry):
        r0, state = carry
        rr = r0 + i
        if not cadence_on:
            state = jax.lax.cond((rr < max_rounds) & jnp.any(state.active),
                                 lambda s: run_round(s, rr), lambda s: s,
                                 state)
            return r0, state
        # cadence: rr is a GLOBAL EVENT STEP.  Which lanes tick is the
        # shared counter-based derivation (battery-paced on the carried
        # levels); a step where nobody ticks only advances the idle
        # counters — the fit/aggregate compute is skipped, not
        # computed-and-discarded, same as the early-exit machinery.
        tick = cadence_mod.tick_mask(cc, rr, arrays["cad_req_ids"],
                                     level=state.level) & state.active

        def step(s):
            return jax.lax.cond(
                jnp.any(tick),
                lambda t: run_round(t, rr, tick),
                lambda t: t._replace(
                    idle=t.idle + (t.active & ~tick).astype(jnp.int32)),
                s)

        state = jax.lax.cond((rr < max_events) & jnp.any(state.active),
                             step, lambda s: s, state)
        return r0, state

    return maybe_round


def _init_state(method, mob, do_refresh, compress, max_rounds, max_events,
                n_params, fc, cc, ac, robust, contrib_flat, arrays):
    """The :class:`FleetCarry` at round 0 — built HOST-SIDE (eagerly) so
    the checkpoint path can serialize/restore exactly this pytree at
    chunk boundaries (field-named ``.npz`` keys, dtype-strict); the
    compiled programs receive it donated.

    Token (1, ...) buffers stand in for state a variant doesn't carry —
    including the per-lane cadence clock fields when ``cc`` is None.
    """
    R, N = contrib_flat.shape[:2]
    P = n_params
    phases = protocol.method_phases(method)
    mobility_on = (mob is not None) and (protocol.Phase.RENEGOTIATE in phases)
    faults_on = (fc is not None) and (protocol.Phase.DELIVER in phases)
    compress_on = compress == "int8"
    cadence_on = cc is not None
    refresh_dedup = do_refresh and not mobility_on and not cadence_on
    if method == "cfl":
        # the shared global model every client fits from each round
        last0 = jnp.broadcast_to(arrays["init_flat"], (R, P)) + 0.0
    elif method == "dfl":
        # node 0's (the requester's) initial params
        last0 = contrib_flat[:, 0]
    else:
        # mobility and fault worlds can aggregate NOTHING in a round
        # (empty neighborhood / all links failed) — the fallback chain
        # must bottom out at the requester's own init, like the loop
        last0 = (jnp.broadcast_to(arrays["init_flat"], (R, P)) + 0.0
                 if (mobility_on or faults_on)
                 else jnp.zeros((R, P), jnp.float32))
    # the carry is DONATED to the programs while ``arrays`` is not — every
    # staged buffer that seeds a carry element is copied (`+ 0`) so no
    # donated input aliases a live one
    clevel0 = (arrays["clevel0"] + 0.0 if mobility_on
               else jnp.zeros((R, N), jnp.float32))
    # per-tile scales travel in the carried state (refresh rewrites
    # them); fp32 runs carry a token buffer
    cscale0 = (arrays["c_scales"] + 0.0 if compress_on
               else jnp.zeros((1, 1, 1), jnp.float32))
    # the dedup'd refresh trajectories (V unique rows), wire-format under
    # compress; token buffers when per-lane refresh (mobility) runs
    if refresh_dedup:
        live0 = (arrays["live_q0"] + 0 if compress_on
                 else arrays["live0"] + 0.0)
        live_s0 = (arrays["live_s0"] + 0.0 if compress_on
                   else jnp.zeros((1, 1), jnp.float32))
    else:
        live0 = jnp.zeros((1, 1), jnp.float32)
        live_s0 = jnp.zeros((1, 1), jnp.float32)
    # the stale-delivery snapshot starts as the handshake staging itself
    # (a round-0 stale hit is a no-op by construction, in both engines)
    if faults_on:
        prev0 = contrib_flat + 0
        prev_s0 = cscale0 + 0.0 if compress_on else jnp.zeros(
            (1, 1, 1), jnp.float32)
    else:
        prev0 = jnp.zeros((1, 1, 1), jnp.float32)
        prev_s0 = jnp.zeros((1, 1, 1), jnp.float32)
    return FleetCarry(
        contrib=contrib_flat,
        cscale=cscale0,
        live=live0,
        live_s=live_s0,
        last=last0,
        level=arrays["level0"] + 0.0,
        active=jnp.ones((R,), bool),
        stop_code=jnp.full((R,), protocol.STOP_MAX_ROUNDS, jnp.int32),
        rounds_done=jnp.zeros((R,), jnp.int32),
        clevel=clevel0,
        acc_h=jnp.zeros((max_rounds, R), jnp.float32),
        loss_h=jnp.zeros((max_rounds, R), jnp.float32),
        bat_h=jnp.zeros((max_rounds, R), jnp.float32),
        exec_h=jnp.zeros((max_rounds, R), jnp.float32),
        # the body trace is per EVENT STEP (== per round in lockstep)
        body_h=jnp.zeros((max_events,), jnp.float32),
        # membership trace; static-world runs carry a token buffer
        # (the mask would just be round_w > 0 replicated per round)
        member_h=jnp.zeros((max_rounds, R, N) if mobility_on else (1, 1, 1),
                           jnp.float32),
        prev=prev0,
        prev_s=prev_s0,
        drop_h=jnp.zeros((max_rounds, R) if faults_on else (1, 1),
                         jnp.float32),
        retry_h=jnp.zeros((max_rounds, R) if faults_on else (1, 1),
                          jnp.float32),
        stale_h=jnp.zeros((max_rounds, R) if faults_on else (1, 1),
                          jnp.float32),
        deliver_h=jnp.zeros((max_rounds, R, N) if faults_on else (1, 1, 1),
                            jnp.float32),
        clock=jnp.zeros((R,) if cadence_on else (1,), jnp.int32),
        idle=jnp.zeros((R,) if cadence_on else (1,), jnp.int32),
        clock_h=jnp.zeros((max_rounds, R) if cadence_on else (1, 1),
                          jnp.int32),
        idle_h=jnp.zeros((max_rounds, R) if cadence_on else (1, 1),
                         jnp.int32),
        corrupt_h=jnp.zeros((max_rounds, R, N) if ac is not None
                            else (1, 1, 1), jnp.float32),
        clip_h=jnp.zeros((max_rounds, R, N) if robust != "none"
                         else (1, 1, 1), jnp.float32))


_FLEET_STATICS = ("task", "use_pallas", "interpret", "do_refresh", "chunk",
                  "max_rounds", "max_events", "epochs", "batch", "steps_max",
                  "ref_epochs", "ref_steps", "spec", "mob", "n_max",
                  "strategy", "compress", "n_params", "method", "fc", "cc",
                  "ac", "robust", "gamma", "n_req", "n_lanes")


@functools.partial(jax.jit, static_argnames=_FLEET_STATICS,
                   donate_argnames=("state",))
def _fleet_program(task, use_pallas, interpret, do_refresh, chunk, max_rounds,
                   max_events, epochs, batch, steps_max, ref_epochs,
                   ref_steps, spec, mob, n_max, strategy, compress, n_params,
                   method, fc, cc, ac, robust, gamma, n_req, n_lanes, state,
                   arrays):
    """The whole fleet's Algorithm 1 as one compiled program.

    Module-level so the jit cache is shared across ``run_fleet`` calls:
    re-running with the same ``task`` (id-hashed static) and the same
    array shapes — e.g. parametrized parity tests sweeping strategies,
    encryption, or stopping thresholds, all of which are traced inputs
    (``round_w``, ``e_round``, ``desired_accuracy``...) — reuses the
    compiled executable instead of re-tracing per call.

    ``state`` is the donated :class:`FleetCarry` from
    :func:`_init_state`; its ``contrib`` field is the flat round state:
    (R, N, P) fp32, or — under ``compress="int8"`` — the (R, N, Lp) int8
    wire payload whose per-tile fp32 scales travel as ``cscale``.
    ``n_params`` is the true flat parameter count P (<= Lp, the
    tile-padded payload length).  ``spec`` is the static
    :func:`repro.utils.tree.tree_ravel` spec that recovers per-device
    parameter pytrees from (P,) lane views.  ``mob`` is the static
    :class:`repro.core.mobility.MobilityConfig` (None = static
    neighborhood); ``fc`` the static
    :class:`repro.core.faults.FaultConfig` (None = perfect links).

    ``method`` selects the traced protocol variant ("enfed", "dfl",
    "cfl" — vocabulary in :func:`repro.core.protocol.method_phases`):
    the per-method phase mask decides at trace time which protocol
    steps are live.  The baseline variants share this program's flat
    round state, batched fedavg kernels, and chunked early-exit loop;
    their round bodies are the loop learners' algorithms phase for
    phase.
    """
    maybe_round = _make_round_fn(
        task, use_pallas, interpret, do_refresh, max_rounds, max_events,
        epochs, batch, steps_max, ref_epochs, ref_steps, spec, mob, n_max,
        strategy, compress, n_params, method, fc, cc, ac, robust, gamma,
        n_req, n_lanes, arrays)

    def while_cond(carry):
        r0, state = carry
        return (r0 < max_events) & jnp.any(state.active)

    def while_body(carry):
        r0, state = carry
        _, state = jax.lax.fori_loop(0, chunk, maybe_round, (r0, state))
        return r0 + chunk, state

    _, state = jax.lax.while_loop(while_cond, while_body,
                                  (jnp.int32(0), state))
    return state


@functools.partial(jax.jit, static_argnames=_FLEET_STATICS,
                   donate_argnames=("state",))
def _fleet_chunk_program(task, use_pallas, interpret, do_refresh, chunk,
                         max_rounds, max_events, epochs, batch, steps_max,
                         ref_epochs, ref_steps, spec, mob, n_max, strategy,
                         compress, n_params, method, fc, cc, ac, robust,
                         gamma, n_req, n_lanes, r0, state, arrays):
    """ONE ``chunk`` of fleet rounds (event steps under cadence), for
    the host-driven checkpoint loop: ``run_fleet(checkpoint_dir=...)``
    calls this per chunk, serializing the returned carry at checkpoint
    boundaries (``repro.checkpoint``).  Traces the SAME ``maybe_round``
    as :func:`_fleet_program` — only the outer while_loop moves to the
    host, so a resumed run replays bit-identical round bodies."""
    maybe_round = _make_round_fn(
        task, use_pallas, interpret, do_refresh, max_rounds, max_events,
        epochs, batch, steps_max, ref_epochs, ref_steps, spec, mob, n_max,
        strategy, compress, n_params, method, fc, cc, ac, robust, gamma,
        n_req, n_lanes, arrays)
    _, state = jax.lax.fori_loop(0, chunk, maybe_round, (r0, state))
    return state


def _jit_cache_size(jit_fn) -> Optional[int]:
    """Compiled-executable count of a jit wrapper, or None where the
    (private, version-dependent) introspection is unavailable."""
    try:
        return int(jit_fn._cache_size())
    except Exception:
        return None


def _note_cache_miss(span, jit_fn, before: Optional[int]) -> None:
    """Annotate a program/chunk span with whether its call compiled
    (cache grew) or reused a warm executable — the compile-vs-warm split
    the bench's wall-clock breakdown is built from."""
    after = _jit_cache_size(jit_fn)
    if before is not None and after is not None:
        span.attrs["cache_miss"] = bool(after > before)


def run_fleet(task, requesters: Sequence[RequesterSpec],
              cfg: Optional[EnFedConfig] = None,
              cost_model: Optional[CostModel] = None,
              use_pallas: bool = True,
              interpret: Optional[bool] = None,
              round_chunk: int = 4,
              method: str = "enfed",
              dfl_topology: str = "mesh",
              checkpoint_dir: Optional[str] = None,
              checkpoint_every: int = 0,
              resume_from: Optional[str] = None,
              timeline: Optional[Timeline] = None,
              trace=None) -> FleetResult:
    """Run ``len(requesters)`` concurrent EnFed sessions as one jit program.

    Note: prefer the :mod:`repro.api` facade
    (``ExecutionSpec(engine="fleet", ...)``) — this function remains the
    engine entrypoint it delegates to.  ``cfg=None`` constructs a fresh
    default config per call (a ``cfg=EnFedConfig()`` default would be one
    import-time mutable instance shared by every caller).

    ``interpret`` selects Pallas interpret mode for the aggregation
    kernel (``None`` = compiled on TPU, interpreted on CPU — see
    ``repro.kernels.common.resolve_interpret``).  ``round_chunk`` is the
    early-exit granularity: the compiled round loop re-checks "is any
    session still active?" every ``round_chunk`` rounds.

    With ``cfg.contributor_refresh_epochs > 0`` each requester's
    ``contributor_states[d]["params"]`` is rebound, as the loop engine's
    refresh does, to that session's final contributor params: read-only
    host (numpy) views of one device-to-host copy of the round state,
    not device arrays.  Requesters that share one states dict see the
    last requester's lanes.

    With ``cfg.mobility`` set, contributor lanes hold each requester's
    candidate pool and membership churns on device — requester lane i
    moves as device ``cfg.mobility.requester_id + i`` in the shared
    kinematics space, so a 1-lane fleet reproduces
    ``EnFedSession.run()`` under the same :class:`MobilityConfig`
    exactly.

    With ``cfg.compress="int8"`` the contributor round state is staged,
    carried, aggregated (fused dequant->fedavg kernel), and refreshed
    entirely in wire format — int8 payload + per-tile fp32 scales — so
    ``staged_param_bytes`` and ``device_round_state_bytes`` drop ~4x on
    tile-amortizing models, and ``CostModel`` prices the compressed
    ``model_bytes`` in every eq. (4)-(7) term.  ``compress="auto"``
    resolves to int8 or fp32 at the tile-padding crossover
    (:func:`repro.kernels.quantize.ops.resolve_compress`) before any of
    that staging happens.

    ``method`` selects the traced protocol variant: ``"enfed"``
    (default, the full Algorithm 1) or the paper's baselines ``"dfl"``
    (gossip mixing over ``dfl_topology`` — "mesh" or "ring") and
    ``"cfl"`` (server-side FedAvg), which run as lanes of the same
    compiled program with the per-method phase mask
    (``protocol.method_phases``) deciding which steps trace.  Baseline
    lanes are the loop learners' client lists (client 0 = the
    requester's own shard, then every in-range neighbor with data);
    their ``SessionResult`` views carry ``battery=None`` and
    ``cfl_session``/``dfl_session`` energy reports, exactly like
    ``repro.api``'s loop-engine baselines.

    With ``cfg.faults`` set, ``Phase.DELIVER`` runs inside the program:
    per-link drop/retry/stale outcomes are derived from the traced round
    number (``repro.core.faults`` — the exact hash chain the loop engine
    evaluates host-side), undelivered links are zeroed out of the fedavg
    weight mask, stale links aggregate the carried round-(r-1) wire
    image, and every drop or retry prices one extra receive window
    through ``CostModel.retry_energy``.

    ``checkpoint_dir`` switches the round loop to a host-driven chunk
    loop that serializes the FULL flat loop carry — wire-format round
    state, batteries, masks, round clocks — via :mod:`repro.checkpoint`
    every ``checkpoint_every`` rounds (default: every ``round_chunk``;
    rounded up to a chunk multiple).  ``resume_from`` restores the
    latest checkpoint in a directory and continues: a run killed at a
    checkpoint boundary and resumed is bit-identical to the
    uninterrupted chunked run (same traced round bodies — only the
    outer while_loop moves to the host).  Checkpointing is an
    enfed-only knob (the baselines' loop oracles have no resumable
    state contract); passing it with ``method != "enfed"`` raises.
    """
    from repro.kernels.common import resolve_interpret

    cfg = cfg if cfg is not None else EnFedConfig()
    cost = cost_model or CostModel()
    protocol.method_phases(method)     # validate the variant name
    R = len(requesters)
    if R == 0:
        raise ValueError("empty fleet")
    if round_chunk < 1:
        raise ValueError(f"round_chunk must be >= 1 (got {round_chunk})")
    if checkpoint_every < 0:
        raise ValueError(
            f"checkpoint_every must be >= 0 (got {checkpoint_every})")
    if (checkpoint_dir or resume_from) and method != "enfed":
        raise ValueError(
            f"checkpointing is enfed-only (got method={method!r})")
    if getattr(cfg, "cadence", None) is not None and method != "enfed":
        raise ValueError(
            f"cadence is enfed-only (got method={method!r}) — the "
            "baselines' loop oracles tick on one global round clock")
    if method != "enfed" and (
            getattr(cfg, "adversary", None) is not None
            or getattr(cfg, "robust", "none") != "none"
            or float(getattr(cfg, "staleness_gamma", 1.0)) != 1.0):
        raise ValueError(
            f"adversary/robust/staleness_gamma are enfed-only (got "
            f"method={method!r}) — the baselines' loop oracles define "
            "their aggregation semantics without Phase.DELIVER")
    # observability: spans are host-side wall clocks only and never feed
    # back into the program (the telemetry house rule); ``trace`` is the
    # opt-in TraceConfig selecting the profiler hook / hlo_stats
    tl = timeline if timeline is not None else Timeline()
    if method != "enfed":
        return _run_fleet_baseline(task, requesters, cfg, cost, method,
                                   dfl_topology, use_pallas, interpret,
                                   round_chunk, timeline=tl, trace=trace)
    mob = cfg.mobility
    fc = cfg.faults
    cc = getattr(cfg, "cadence", None)
    # the global event-step budget the program loops over; lockstep is
    # the special case max_events == max_rounds (one step per round)
    max_events = (cadence_mod.events_budget(cc, cfg.max_rounds)
                  if cc is not None else cfg.max_rounds)
    _sp_stage = tl.begin("stage")

    # ---- Phase.HANDSHAKE (host-side, static) ------------------------------
    _sp = tl.begin("handshake")
    # Static world: sign utility-ranked contracts once.  Mobility: fix the
    # candidate POOL (agreeing devices, stable device order — the lane
    # order of both engines); membership is re-negotiated per round on
    # device by mobility.membership_step.
    if mob is None:
        contracts, contract_mask = sign_contracts_fleet(
            [spec.neighborhood for spec in requesters],
            cfg.offered_incentive, cfg.n_max)
        lane_devs = contracts
    else:
        lane_devs = [candidate_pool(spec.neighborhood, cfg.offered_incentive)
                     for spec in requesters]
    for i, cs in enumerate(lane_devs):
        if not cs:
            raise RuntimeError(
                f"requester {i}: no nearby device agreed to the incentive (N_d < 1)")
    N = (contract_mask.shape[1] if mob is None
         else max(len(cs) for cs in lane_devs))

    if mob is None:
        # per-round aggregation weights = contract mask x strategy round mask
        round_w = np.zeros((R, N), np.float32)
        for i, cs in enumerate(lane_devs):
            round_w[i, :len(cs)] = protocol.round_weights(len(cs), cfg.strategy)
    else:
        # membership (and therefore the weight vector) is traced; stage
        # the static candidate descriptors instead
        req_ids = np.array([mob.requester_id + i for i in range(R)], np.int32)
        cand_ids = np.zeros((R, N), np.int32)
        cand_mask = np.zeros((R, N), bool)
        base_util = np.zeros((R, N), np.float32)
        clevel0 = np.zeros((R, N), np.float32)
        for i, cs in enumerate(lane_devs):
            n_i = len(cs)
            max_data = max(d.data_size for d in cs)
            cand_ids[i, :n_i] = [d.device_id for d in cs]
            cand_mask[i, :n_i] = True
            clevel0[i, :n_i] = [d.battery_level for d in cs]
            # one vectorized call per requester, the same arithmetic the
            # loop engine's _run_mobility stages
            base_util[i, :n_i] = np.asarray(mobility_mod.static_utility_term(
                np.array([d.model_staleness for d in cs], np.float32),
                np.array([d.data_size for d in cs], np.float32),
                np.float32(max_data)), np.float32)
    tl.finish(_sp)

    # ---- contributor state / data stacks ----------------------------------
    _sp = tl.begin("shards")
    # Shared shards are deduplicated: each unique (device, shard) pair is
    # staged once into a table, lanes carry gather indices.  At R=512
    # with one shared contributor population this removes the dominant
    # host->device transfer (the ROADMAP's cx item).
    template = requesters[0].contributor_states[
        lane_devs[0][0].device_id]["params"]
    contrib_params = []
    shard_rows: dict = {}
    shard_x, shard_y = [], []
    cidx = np.zeros((R, N), np.int32)
    shard_len = np.zeros((R, N), np.int32)
    for i, (spec, cs) in enumerate(zip(requesters, lane_devs)):
        row_p = []
        for j, c in enumerate(cs):
            st = spec.contributor_states[c.device_id]
            row_p.append(st["params"])
            xa = np.ascontiguousarray(st["data"][0], np.float32)
            ya = np.ascontiguousarray(st["data"][1], np.int32)
            # content identity, not object identity: deep-copied
            # contributor_states (the common RequesterSpec pattern) must
            # still collapse to one staged shard per device.  Full
            # 128-bit digests, not Python hash(): a 64-bit hash over a
            # long-lived population could silently alias two distinct
            # shards onto one staged row
            key = (c.device_id, xa.shape,
                   hashlib.blake2b(xa.tobytes(), digest_size=16).digest(),
                   hashlib.blake2b(ya.tobytes(), digest_size=16).digest())
            row = shard_rows.get(key)
            if row is None:
                row = len(shard_x)
                shard_rows[key] = row
                shard_x.append(xa)
                shard_y.append(ya)
            cidx[i, j] = row
            shard_len[i, j] = len(shard_x[row])
        contrib_params.append(row_p)

    n_c_max = max(len(x) for x in shard_x)
    U = len(shard_x)
    cx_tab = np.zeros((U, n_c_max) + shard_x[0].shape[1:], np.float32)
    cy_tab = np.zeros((U, n_c_max), np.int32)
    for u, (x, y) in enumerate(zip(shard_x, shard_y)):
        cx_tab[u, :len(x)] = x
        cy_tab[u, :len(y)] = y
    tl.finish(_sp, lanes=int(sum(len(cs) for cs in lane_devs)), shards=U)
    _sp = tl.begin("stack")
    padded_rows = [row + [None] * (N - len(row)) for row in contrib_params]
    contrib_stack = _stack_trees(
        [_stack_trees(row, template) for row in padded_rows])
    # the flat-parameter round state: raveled ONCE here, donated to the
    # program, carried flat through every round.  Under compress="int8"
    # it is quantized ONCE here too — the program is staged (and runs)
    # entirely on the wire-format payload + per-tile scales.
    contrib_flat, ravel_spec = tree_ravel(contrib_stack, batch_ndim=2)
    P = contrib_flat.shape[-1]
    # "auto" resolves to a concrete wire format here, from the flat
    # model size — the same resolution EnFedSession and the cost model
    # apply, so all paths land on one side of the crossover together
    wire_compress = resolve_compress(cfg.compress, P)
    # fp32 lane rows, kept host-side for the refresh-dedup key/live rows
    # (the donated buffer below may be quantized); cadence runs keep the
    # per-lane refresh path — contributor ticks desynchronize lanes
    contrib_np = (np.asarray(contrib_flat)
                  if (cfg.contributor_refresh_epochs > 0 and mob is None
                      and cc is None)
                  else None)
    tl.finish(_sp)
    c_scales = None
    if wire_compress == "int8":
        lp = padded_len(P)
        with tl.span("quantize_pack", what="round_state"):
            q0, s0 = quantize_flat_batched(
                jnp.pad(contrib_flat, ((0, 0), (0, 0), (0, lp - P)))
                .reshape(R * N, lp),
                use_pallas=use_pallas, interpret=interpret)
            jax.block_until_ready(q0)
        contrib_flat = q0.reshape(R, N, lp)
        c_scales = s0.reshape(R, N, -1)
        staged_param_bytes = int(contrib_flat.nbytes + c_scales.nbytes)
    else:
        staged_param_bytes = int(contrib_flat.nbytes)
    device_round_state_bytes = staged_param_bytes

    # ---- requester data + derived-schedule metadata -----------------------
    _sp_arrays = tl.begin("arrays")
    own_x, _ = _pad_stack([np.asarray(s.own_train[0], np.float32) for s in requesters],
                          max(len(s.own_train[0]) for s in requesters))
    own_y, _ = _pad_stack([np.asarray(s.own_train[1], np.int32) for s in requesters],
                          own_x.shape[1])
    test_x, test_mask = _pad_stack([np.asarray(s.own_test[0], np.float32) for s in requesters],
                                   max(len(s.own_test[0]) for s in requesters))
    test_y, _ = _pad_stack([np.asarray(s.own_test[1], np.int32) for s in requesters],
                           test_x.shape[1])

    n_own = np.array([len(s.own_train[0]) for s in requesters], np.int32)
    steps_max = max(schedule.fit_steps(int(n), cfg.batch_size) for n in n_own)

    ref_epochs = max(cfg.contributor_refresh_epochs, 0)
    ref_steps = max((schedule.fit_steps(int(n), cfg.batch_size)
                     for n in shard_len[shard_len > 0]), default=1)
    ref_seeds = np.zeros((R, N), np.int32)
    for i, cs in enumerate(lane_devs):
        for j, c in enumerate(cs):
            ref_seeds[i, j] = cfg.seed + c.device_id

    # ---- Phase.ACCOUNT constants (static per requester) -------------------
    num_params = tree_size(template)
    model_bytes = update_wire_bytes(num_params, encrypt=cfg.encrypt,
                                    compress=wire_compress,
                                    raw_bytes=tree_bytes(template))
    batteries = [s.battery or BatteryState() for s in requesters]
    if mob is None and fc is None:
        e_round = np.array([cost.round_energy(
            n_contrib=len(cs), num_params=num_params, model_bytes=model_bytes,
            num_samples=len(spec.own_train[0]), epochs=cfg.epochs,
            n_devices=len(spec.neighborhood), encrypt=cfg.encrypt)
            for spec, cs in zip(requesters, lane_devs)], np.float32)
    elif mob is None:
        # static world + faults: the DELIVERED count is traced, so the
        # round energy becomes the same per-count lookup mobility uses
        # (table entries are round_energy(n_contrib=j) — independent of
        # the table width, so they match the loop engine's per-requester
        # tables entry for entry)
        e_tab = np.array([cost.round_energy_table(
            max_contrib=N, num_params=num_params, model_bytes=model_bytes,
            num_samples=len(spec.own_train[0]), epochs=cfg.epochs,
            n_devices=len(spec.neighborhood), encrypt=cfg.encrypt)
            for spec in requesters], np.float32)
        init_params = task.init(seed=cfg.seed)
        init_flat, _ = tree_ravel(init_params)
    else:
        # member count is traced -> per-count lookup table, plus the
        # contributor-side per-round energy split (tx / refresh)
        e_tab = np.array([cost.round_energy_table(
            max_contrib=N, num_params=num_params, model_bytes=model_bytes,
            num_samples=len(spec.own_train[0]), epochs=cfg.epochs,
            n_devices=len(spec.neighborhood), encrypt=cfg.encrypt)
            for spec in requesters], np.float32)
        e_tx = np.zeros((R, N), np.float32)
        e_ref = np.zeros((R, N), np.float32)
        for i, cs in enumerate(lane_devs):
            for j in range(len(cs)):
                e_tx[i, j], e_ref[i, j] = cost.contributor_round_energy(
                    num_params=num_params, model_bytes=model_bytes,
                    num_samples=int(shard_len[i, j]),
                    refresh_epochs=cfg.contributor_refresh_epochs,
                    encrypt=cfg.encrypt)
        init_params = task.init(seed=cfg.seed)
        init_flat, _ = tree_ravel(init_params)
    capacity = np.array([b.capacity_j for b in batteries], np.float32)
    level0 = np.array([b.level for b in batteries], np.float32)
    eff = np.array([load_efficiency(cost.device.p_train, b.high_load_penalty,
                                    b.high_load_threshold_w) for b in batteries],
                   np.float32)

    # ---- the compiled program ---------------------------------------------
    arrays = dict(
        level0=jnp.asarray(level0), own_x=jnp.asarray(own_x),
        own_y=jnp.asarray(own_y), test_x=jnp.asarray(test_x),
        test_y=jnp.asarray(test_y), test_mask=jnp.asarray(test_mask),
        n_own=jnp.asarray(n_own), seed0=jnp.int32(cfg.seed),
        capacity=jnp.asarray(capacity), eff=jnp.asarray(eff),
        desired_accuracy=jnp.float32(cfg.desired_accuracy),
        battery_threshold=jnp.float32(cfg.battery_threshold))
    if mob is None:
        arrays.update(round_w=jnp.asarray(round_w))
        if fc is None:
            arrays.update(e_round=jnp.asarray(e_round))
        else:
            arrays.update(e_tab=jnp.asarray(e_tab),
                          init_flat=jnp.asarray(init_flat))
    else:
        arrays.update(req_ids=jnp.asarray(req_ids),
                      cand_ids=jnp.asarray(cand_ids),
                      cand_mask=jnp.asarray(cand_mask),
                      base_util=jnp.asarray(base_util),
                      clevel0=jnp.asarray(clevel0),
                      e_tab=jnp.asarray(e_tab), e_tx=jnp.asarray(e_tx),
                      e_ref=jnp.asarray(e_ref),
                      init_flat=jnp.asarray(init_flat))
    if c_scales is not None:
        arrays.update(c_scales=c_scales)
    if fc is not None:
        # Phase.DELIVER staging: lane i rolls fault dice as requester
        # ``fc.requester_id + i`` (the api loop path hands requester i a
        # config with exactly that id, so engines agree per requester);
        # links are the signed lanes (static) or the candidate pool
        # (mobility — membership already masks attempts per round).
        freq_ids = np.array([fc.requester_id + i for i in range(R)], np.int32)
        fcand_ids = np.zeros((R, N), np.int32)
        fsigned = np.zeros((R, N), bool)
        for i, cs in enumerate(lane_devs):
            fcand_ids[i, :len(cs)] = [d.device_id for d in cs]
            fsigned[i, :len(cs)] = True
        e_rx_retry, _, t_retry = cost.retry_energy(
            model_bytes=model_bytes, encrypt=cfg.encrypt)
        arrays.update(freq_ids=jnp.asarray(freq_ids),
                      fcand_ids=jnp.asarray(fcand_ids),
                      e_retry=jnp.float32(e_rx_retry))
        if mob is None:
            arrays.update(fsigned=jnp.asarray(fsigned))
    if cc is not None:
        # cadence staging: lane i's requester ticks as device
        # ``cc.requester_id + i`` (the api loop path hands requester i a
        # config with exactly that id); contributors tick by their REAL
        # device ids — a device's cadence is a property of the device,
        # not of the session observing it.  ``cad_signed`` masks padded
        # contributor slots out of the refresh gate in static worlds.
        cad_req_ids = np.array([cc.requester_id + i for i in range(R)],
                               np.int32)
        cad_cand_ids = np.zeros((R, N), np.int32)
        cad_signed = np.zeros((R, N), bool)
        for i, cs in enumerate(lane_devs):
            cad_cand_ids[i, :len(cs)] = [d.device_id for d in cs]
            cad_signed[i, :len(cs)] = True
        arrays.update(cad_req_ids=jnp.asarray(cad_req_ids),
                      cad_cand_ids=jnp.asarray(cad_cand_ids),
                      cad_signed=jnp.asarray(cad_signed))
    ac = getattr(cfg, "adversary", None)
    if ac is not None:
        # adversary staging: lane i rolls corruption dice as requester
        # ``ac.requester_id + i`` (the api loop path hands requester i a
        # config with exactly that id); links key on the contributors'
        # REAL device ids — which devices are Byzantine is a property of
        # the world, observed identically by every session.  ``asigned``
        # masks padded lanes out of the corrupted trace rows.
        areq_ids = np.array([ac.requester_id + i for i in range(R)], np.int32)
        acand_ids = np.zeros((R, N), np.int32)
        asigned = np.zeros((R, N), bool)
        for i, cs in enumerate(lane_devs):
            acand_ids[i, :len(cs)] = [d.device_id for d in cs]
            asigned[i, :len(cs)] = True
        arrays.update(areq_ids=jnp.asarray(areq_ids),
                      acand_ids=jnp.asarray(acand_ids),
                      asigned=jnp.asarray(asigned))
    if ref_epochs > 0:
        arrays.update(cx_tab=jnp.asarray(cx_tab), cy_tab=jnp.asarray(cy_tab))
    tl.finish(_sp_arrays)
    shard_bytes = shard_bytes_dense = 0
    gather_bytes = gather_bytes_dense = 0
    index_bytes = int(n_own.nbytes + 4)
    if ref_epochs > 0:
        if mob is None and cc is None:
            # refresh-COMPUTE dedup: lanes subscribed to the same
            # (device, shard content, staged params) follow identical
            # trajectories in a static world, so one live row per unique
            # subscription is trained and scattered to its lanes
            _sp = tl.begin("refresh_dedup")
            ref_map: dict = {}
            ref_uidx = np.zeros((R, N), np.int32)
            lane_valid = np.zeros((R, N), bool)
            u_cidx, u_n, u_seed, rep_i, rep_j = [], [], [], [], []
            for i, cs in enumerate(lane_devs):
                for j, c in enumerate(cs):
                    key = (c.device_id, int(cidx[i, j]),
                           hashlib.blake2b(contrib_np[i, j].tobytes(),
                                           digest_size=16).digest())
                    v = ref_map.get(key)
                    if v is None:
                        v = len(u_cidx)
                        ref_map[key] = v
                        u_cidx.append(int(cidx[i, j]))
                        u_n.append(int(shard_len[i, j]))
                        u_seed.append(cfg.seed + c.device_id)
                        rep_i.append(i)
                        rep_j.append(j)
                    ref_uidx[i, j] = v
                    lane_valid[i, j] = True
            V = len(u_cidx)
            live0 = jnp.asarray(contrib_np[rep_i, rep_j])   # (V, P) fp32
            arrays.update(u_cidx=jnp.asarray(np.array(u_cidx, np.int32)),
                          u_n=jnp.asarray(np.array(u_n, np.int32)),
                          u_seed=jnp.asarray(np.array(u_seed, np.int32)),
                          ref_uidx=jnp.asarray(ref_uidx),
                          lane_valid=jnp.asarray(lane_valid))
            if wire_compress == "int8":
                lp = padded_len(P)
                with tl.span("quantize_pack", what="live_rows"):
                    lq, ls = quantize_flat_batched(
                        jnp.pad(live0, ((0, 0), (0, lp - P))),
                        use_pallas=use_pallas, interpret=interpret)
                    jax.block_until_ready(lq)
                arrays.update(live_q0=lq, live_s0=ls)
            else:
                arrays.update(live0=live0)
            tl.finish(_sp, live_rows=V)
            ref_lanes = V
            idx_meta = int(ref_uidx.nbytes + 4 * 3 * V)
        else:
            arrays.update(cidx=jnp.asarray(cidx),
                          ref_seeds=jnp.asarray(ref_seeds),
                          ref_n=jnp.asarray(shard_len))
            ref_lanes = R * N
            idx_meta = int(ref_seeds.nbytes + shard_len.nbytes)
        # shard-table accounting: gather indices live with the shards
        # (cidx/ref_uidx only count here); schedule metadata is separate
        shard_bytes = int(cx_tab.nbytes + cy_tab.nbytes + cidx.nbytes)
        shard_bytes_dense = int(R * N * (cx_tab.nbytes + cy_tab.nbytes)
                                / max(U, 1))
        index_bytes += idx_meta
        # refresh device-memory accounting: the per-step (ref_lanes, B)
        # table gather vs the old lane-dense (R*N, n_c, F) block
        sample_bytes = int((cx_tab.nbytes + cy_tab.nbytes)
                           // max(U * n_c_max, 1))
        gather_bytes = int(ref_lanes * cfg.batch_size * sample_bytes)
        gather_bytes_dense = shard_bytes_dense
    staged = [contrib_flat] + [v for v in arrays.values() if hasattr(v, "nbytes")]
    staged_bytes = int(sum(int(v.nbytes) for v in staged))
    tl.spans[_sp_arrays].attrs["bytes"] = staged_bytes

    robust = getattr(cfg, "robust", "none")
    gamma = float(getattr(cfg, "staleness_gamma", 1.0))
    statics = (task, use_pallas, resolve_interpret(interpret), ref_epochs > 0,
               int(round_chunk), cfg.max_rounds, max_events, cfg.epochs,
               cfg.batch_size, steps_max, ref_epochs, ref_steps, ravel_spec,
               mob, cfg.n_max, cfg.strategy if mob is not None else None,
               wire_compress, P, "enfed", fc, cc, ac, robust, gamma, R, N)
    with tl.span("init_state"):
        state = _init_state("enfed", mob, ref_epochs > 0, wire_compress,
                            cfg.max_rounds, max_events, P, fc, cc, ac, robust,
                            contrib_flat, arrays)
    tl.finish(_sp_stage)
    hlo = None
    if trace is not None and getattr(trace, "hlo_stats", False):
        # AOT lower+compile BEFORE the donating call: lowering only reads
        # abstract shapes, so the donated carry buffers stay intact
        with tl.span("hlo_stats"):
            hlo = jit_hlo_stats(_fleet_program, *statics, state, arrays) or None
    if checkpoint_dir or resume_from:
        # host-driven chunk loop: same traced round bodies, the outer
        # while moves to the host so the carry can be serialized (and a
        # killed run restarted) at chunk boundaries
        from repro import checkpoint as ckpt_mod
        chunk = int(round_chunk)
        every = checkpoint_every if checkpoint_every > 0 else chunk
        every = ((every + chunk - 1) // chunk) * chunk   # chunk multiple
        r0 = 0
        if resume_from:
            with tl.span("checkpoint_restore"):
                template = {"r0": np.int64(0),
                            "state": jax.tree_util.tree_map(np.asarray, state)}
                pay, _step = ckpt_mod.restore_checkpoint(resume_from, template)
            r0 = int(pay["r0"])
            state = jax.tree_util.tree_map(jnp.asarray, pay["state"])
        while r0 < max_events and bool(np.any(np.asarray(state.active))):
            before = _jit_cache_size(_fleet_chunk_program)
            _sp = tl.begin("chunk", r0=r0)
            state = _fleet_chunk_program(*statics, jnp.int32(r0), state,
                                         arrays)
            jax.block_until_ready(state)
            _note_cache_miss(tl.spans[_sp], _fleet_chunk_program, before)
            tl.finish(_sp)
            r0 += chunk
            if checkpoint_dir and r0 % every == 0:
                with tl.span("checkpoint_save", r0=r0):
                    ckpt_mod.save_checkpoint(
                        checkpoint_dir, r0,
                        {"r0": np.int64(r0),
                         "state": jax.tree_util.tree_map(np.asarray,
                                                         state)})
    else:
        before = _jit_cache_size(_fleet_program)
        _sp = tl.begin("program")
        state = _fleet_program(*statics, state, arrays)
        jax.block_until_ready(state)
        _note_cache_miss(tl.spans[_sp], _fleet_program, before)
        tl.finish(_sp)
    _sp_unpack = tl.begin("unpack")
    _sp = tl.begin("fetch")
    contrib_final, cscale_final = state.contrib, state.cscale
    last_flat = state.last
    acc_h, loss_h, bat_h, exec_h, body_h, member_h = (
        np.asarray(t) for t in (state.acc_h, state.loss_h, state.bat_h,
                                state.exec_h, state.body_h, state.member_h))
    if fc is not None:
        drop_h, retry_h, stale_h, deliver_h = (
            np.asarray(t) for t in (state.drop_h, state.retry_h,
                                    state.stale_h, state.deliver_h))
    if cc is not None:
        clock_h = np.asarray(state.clock_h)
        idle_h = np.asarray(state.idle_h)
        idle_fin = np.asarray(state.idle)
    if ac is not None:
        corrupt_h = np.asarray(state.corrupt_h)
    if robust != "none":
        clip_h = np.asarray(state.clip_h)
    rounds_np = np.asarray(state.rounds_done)
    codes_np = np.asarray(state.stop_code)
    level_np = np.asarray(state.level)
    tl.finish(_sp)

    # contributor write-back: like the loop engine's in-place refresh,
    # each requester's contributor_states end up holding that session's
    # final (refresh-trained, frozen-once-stopped) contributor params.
    # Requesters sharing one states dict see the last writer's lanes.
    # Under compress the final state is wire format — the write-back is
    # its dequantized image, exactly what the loop engine leaves behind.
    # The (R, N, P) state comes to the host in ONE transfer; each lane's
    # tree is numpy views of the unraveled leaves, with no device
    # dispatch.  The leaves are read-only: every lane's tree is a view of
    # them, and states dicts hold param trees as immutable values
    # (WorldSpec.fresh_requesters shares them between runs).
    if ref_epochs > 0:
        _sp = tl.begin("writeback")
        if wire_compress == "int8":
            with tl.span("dequant_unpack"):
                contrib_final = dequantize_flat_batched(
                    contrib_final, cscale_final)[..., :P]
                jax.block_until_ready(contrib_final)
        contrib_host = np.asarray(contrib_final)
        contrib_tree = tree_unravel(ravel_spec, contrib_host)
        for leaf in jax.tree_util.tree_leaves(contrib_tree):
            leaf.setflags(write=False)
        for i, (spec, cs) in enumerate(zip(requesters, lane_devs)):
            for j, c in enumerate(cs):
                spec.contributor_states[c.device_id]["params"] = (
                    jax.tree_util.tree_map(lambda l: l[i, j], contrib_tree))
        tl.finish(_sp, views=int(sum(len(cs) for cs in lane_devs)),
                  bytes=int(contrib_host.nbytes))

    with tl.span("unravel"):
        last_p = tree_unravel(ravel_spec, last_flat)
    tl.finish(_sp_unpack)

    # ---- per-session views (loop-engine-compatible SessionResults) --------
    _sp = tl.begin("views", sessions=R)
    sessions = []
    total_e = 0.0
    for i, (spec, cs, b0) in enumerate(zip(requesters, lane_devs, batteries)):
        r_i = int(rounds_np[i])
        if mob is None:
            n_contrib_i = float(len(cs))
        else:
            # mobility: energy roll-up over the MEAN membership, matching
            # EnFedSession._run_mobility's report
            n_contrib_i = (float(member_h[:r_i, i].sum(-1).mean())
                           if r_i else 0.0)
        report = cost.session(
            rounds=r_i, n_contrib=n_contrib_i, num_params=num_params,
            model_bytes=model_bytes, num_samples=len(spec.own_train[0]),
            epochs=cfg.epochs, n_devices=len(spec.neighborhood),
            encrypt=cfg.encrypt)
        if fc is not None:
            # the traces alone reconstruct the fault transport overhead:
            # every drop or retry burned one extra receive window
            extra_i = float(drop_h[:r_i, i].sum() + retry_h[:r_i, i].sum())
            if extra_i:
                report.times.t_com += extra_i * t_retry
                report.e_comm += extra_i * e_rx_retry
        if cc is not None:
            # idle/duty-cycle windows priced through the one shared
            # helper, post-hoc like the retry windows: per-round waits
            # from the trace plus the trailing idle of a lane that never
            # finished.  Idle never drains the simulated battery.
            total_idle_i = int(idle_h[:r_i, i].sum()) + int(idle_fin[i])
            if total_idle_i:
                e_idle, t_idle = cost.idle_energy(
                    idle_steps=total_idle_i, idle_step_s=cc.idle_step_s)
                report.times.t_com += t_idle
                report.e_comm += e_idle
        if robust != "none" and r_i:
            # robust-screening compute priced post-hoc like retry/idle
            # windows: one scan of the session's lane buffer per
            # executed round, into the aggregation time/energy terms —
            # never the simulated battery (so defended and undefended
            # runs of the same world keep bitwise-equal battery traces)
            e_scr, t_scr = cost.screening_energy(
                n_contrib=len(cs), num_params=num_params)
            report.times.t_agg += r_i * t_scr
            report.e_comp += r_i * e_scr
        total_e += report.e_tot
        battery = dataclasses.replace(b0, level=float(level_np[i]))
        history = {"accuracy": [float(a) for a in acc_h[:r_i, i]],
                   "loss": [float(l) for l in loss_h[:r_i, i]],
                   "battery": [float(l) for l in bat_h[:r_i, i]],
                   "round_executed": [float(x) for x in exec_h[:r_i, i]]}
        if mob is not None:
            history["member_mask"] = [member_h[r, i].copy()
                                      for r in range(r_i)]
            history["members"] = [float(member_h[r, i].sum())
                                  for r in range(r_i)]
        if fc is not None:
            history["drops"] = [float(x) for x in drop_h[:r_i, i]]
            history["retries"] = [float(x) for x in retry_h[:r_i, i]]
            history["stale"] = [float(x) for x in stale_h[:r_i, i]]
            history["deliver_mask"] = [deliver_h[r, i].copy()
                                       for r in range(r_i)]
        if cc is not None:
            history["round_clock"] = [int(x) for x in clock_h[:r_i, i]]
            history["idle_steps"] = [int(x) for x in idle_h[:r_i, i]]
        if ac is not None:
            history["corrupted_mask"] = [corrupt_h[r, i].copy()
                                         for r in range(r_i)]
        if robust != "none":
            history["clipped_mask"] = [clip_h[r, i].copy()
                                       for r in range(r_i)]
        sessions.append(SessionResult(
            accuracy=history["accuracy"][-1] if history["accuracy"] else 0.0,
            rounds=r_i, n_contributors=len(cs), report=report, battery=battery,
            history=history, stop_reason=protocol.stop_reason_name(codes_np[i]),
            params=jax.tree_util.tree_map(lambda l: l[i], last_p),
            model_bytes=model_bytes))
    fleet_hist = {"accuracy": acc_h, "loss": loss_h, "battery": bat_h,
                  "executed": exec_h, "round_executed": body_h,
                  "member": member_h}
    if fc is not None:
        fleet_hist.update(drops=drop_h, retries=retry_h, stale=stale_h,
                          deliver=deliver_h)
    if cc is not None:
        fleet_hist.update(round_clock=clock_h, idle_steps=idle_h)
    if ac is not None:
        fleet_hist.update(corrupted=corrupt_h)
    if robust != "none":
        fleet_hist.update(clipped=clip_h)
    tl.finish(_sp)
    return FleetResult(
        sessions=sessions, rounds=rounds_np, stop_codes=codes_np,
        accuracy=np.array([s.accuracy for s in sessions], np.float32),
        battery_level=level_np, total_energy_j=float(total_e),
        history=fleet_hist,
        staged_host_bytes=staged_bytes, staged_index_bytes=index_bytes,
        staged_shard_bytes=shard_bytes,
        staged_shard_bytes_dense=shard_bytes_dense,
        staged_param_bytes=staged_param_bytes,
        device_round_state_bytes=device_round_state_bytes,
        refresh_gather_bytes=gather_bytes,
        refresh_gather_bytes_dense=gather_bytes_dense,
        timeline=tl, hlo_stats=hlo)


def _run_fleet_baseline(task, requesters: Sequence[RequesterSpec], cfg, cost,
                        method: str, dfl_topology: str, use_pallas: bool,
                        interpret, round_chunk: int,
                        timeline: Optional[Timeline] = None,
                        trace=None) -> FleetResult:
    """Stage and run the dfl/cfl traced protocol variants.

    Client roster of requester i = [own shard] + every in-range neighbor
    with data, in neighborhood order — exactly ``WorldSpec.client_data``
    and therefore the loop learners' ``client_data`` list.  Shards are
    content-deduplicated into the same unique-table + gather-index form
    the enfed path stages; node params are the flat (R, N, P) round
    state.  Mobility, refresh, compression-of-state, and battery do not
    exist for the baselines (their loop oracles have none), so those
    knobs are stripped before tracing; ``cfg.compress`` still prices the
    wire in the cost domain, matching the loop learners.
    """
    from repro.kernels.common import resolve_interpret

    if dfl_topology not in ("mesh", "ring"):
        raise ValueError(f"unknown dfl topology {dfl_topology!r} (mesh|ring)")
    tl = timeline if timeline is not None else Timeline()
    _sp_stage = tl.begin("stage")
    R = len(requesters)

    # ---- client rosters (the loop learners' client_data lists) ------------
    rosters = []
    for spec in requesters:
        shards = [spec.own_train]
        for dev in spec.neighborhood:
            st = spec.contributor_states.get(dev.device_id)
            if st is not None:
                shards.append(st["data"])
        rosters.append(shards)
    N = max(len(s) for s in rosters)

    # ---- deduplicated shard table + per-lane gather indices ---------------
    shard_rows: dict = {}
    shard_x, shard_y = [], []
    cidx = np.zeros((R, N), np.int32)
    cli_n = np.zeros((R, N), np.int32)
    for i, shards in enumerate(rosters):
        for j, (xs, ys) in enumerate(shards):
            xa = np.ascontiguousarray(xs, np.float32)
            ya = np.ascontiguousarray(ys, np.int32)
            key = (xa.shape,
                   hashlib.blake2b(xa.tobytes(), digest_size=16).digest(),
                   hashlib.blake2b(ya.tobytes(), digest_size=16).digest())
            row = shard_rows.get(key)
            if row is None:
                row = len(shard_x)
                shard_rows[key] = row
                shard_x.append(xa)
                shard_y.append(ya)
            cidx[i, j] = row
            cli_n[i, j] = len(xa)
    U = len(shard_x)
    n_c_max = max(len(x) for x in shard_x)
    cx_tab = np.zeros((U, n_c_max) + shard_x[0].shape[1:], np.float32)
    cy_tab = np.zeros((U, n_c_max), np.int32)
    for u, (x, y) in enumerate(zip(shard_x, shard_y)):
        cx_tab[u, :len(x)] = x
        cy_tab[u, :len(y)] = y

    # ---- node params: the flat (R, N, P) round state -----------------------
    template = task.init(seed=cfg.seed)
    init_flat, ravel_spec = tree_ravel(template)
    P = int(init_flat.shape[-1])
    if method == "dfl":
        # DFLLearner: node j of every requester inits from seed + j
        node_inits = jnp.stack(
            [init_flat] + [tree_ravel(task.init(seed=cfg.seed + j))[0]
                           for j in range(1, N)])
        contrib_flat = jnp.broadcast_to(node_inits[None], (R, N, P)) + 0.0
    else:
        # CFL carries ONE global (in `last`); the lane buffer holds the
        # current round's fitted client updates
        contrib_flat = jnp.zeros((R, N, P), jnp.float32)

    # ---- aggregation weights ----------------------------------------------
    if method == "cfl":
        # CFLLearner weights clients by shard size; padded lanes weigh 0
        cli_w = cli_n.astype(np.float32)
    else:
        strategy = topology.AggregationStrategy(
            kind="dfl_mesh" if dfl_topology == "mesh" else "dfl_ring")
        mix_w = np.zeros((R, N, N), np.float32)
        for i, shards in enumerate(rosters):
            n_i = len(shards)
            mix_w[i, :n_i, :n_i] = topology.group_mixing_matrix(n_i, strategy)
            for k in range(n_i, N):
                mix_w[i, k, k] = 1.0    # padded lanes mix with themselves

    # ---- requester test stacks + schedule bounds --------------------------
    test_x, test_mask = _pad_stack(
        [np.asarray(s.own_test[0], np.float32) for s in requesters],
        max(len(s.own_test[0]) for s in requesters))
    test_y, _ = _pad_stack(
        [np.asarray(s.own_test[1], np.int32) for s in requesters],
        test_x.shape[1])
    steps_max = max(schedule.fit_steps(int(n), cfg.batch_size)
                    for n in cli_n[cli_n > 0])

    arrays = dict(
        cx_tab=jnp.asarray(cx_tab), cy_tab=jnp.asarray(cy_tab),
        cidx=jnp.asarray(cidx), cli_n=jnp.asarray(cli_n),
        test_x=jnp.asarray(test_x), test_y=jnp.asarray(test_y),
        test_mask=jnp.asarray(test_mask), seed0=jnp.int32(cfg.seed),
        desired_accuracy=jnp.float32(cfg.desired_accuracy),
        level0=jnp.ones((R,), jnp.float32))
    if method == "cfl":
        arrays.update(cli_w=jnp.asarray(cli_w), init_flat=init_flat)
    else:
        arrays.update(mix_w=jnp.asarray(mix_w))
    staged_param_bytes = int(contrib_flat.nbytes)
    shard_bytes = int(cx_tab.nbytes + cy_tab.nbytes + cidx.nbytes)
    shard_bytes_dense = int(R * N * (cx_tab.nbytes + cy_tab.nbytes)
                            / max(U, 1))
    index_bytes = int(cli_n.nbytes + cidx.nbytes + 4)
    staged = [contrib_flat] + [v for v in arrays.values()
                               if hasattr(v, "nbytes")]
    staged_bytes = int(sum(int(v.nbytes) for v in staged))

    state0 = _init_state(method, None, False, None, cfg.max_rounds,
                         cfg.max_rounds, P, None, None, None, "none",
                         contrib_flat, arrays)
    statics = (task, use_pallas, resolve_interpret(interpret), False,
               int(round_chunk), cfg.max_rounds, cfg.max_rounds, cfg.epochs,
               cfg.batch_size, steps_max, 0, 1, ravel_spec, None, cfg.n_max,
               None, None, P, method, None, None, None, "none", 1.0, R, N)
    tl.finish(_sp_stage)
    hlo = None
    if trace is not None and getattr(trace, "hlo_stats", False):
        with tl.span("hlo_stats"):
            hlo = jit_hlo_stats(_fleet_program, *statics, state0, arrays) or None
    before = _jit_cache_size(_fleet_program)
    _sp = tl.begin("program")
    state = _fleet_program(*statics, state0, arrays)
    jax.block_until_ready(state)
    _note_cache_miss(tl.spans[_sp], _fleet_program, before)
    tl.finish(_sp)
    _sp_unpack = tl.begin("unpack")
    last_flat, level = state.last, state.level
    acc_h, loss_h, bat_h, exec_h, body_h, member_h = (
        np.asarray(t) for t in (state.acc_h, state.loss_h, state.bat_h,
                                state.exec_h, state.body_h, state.member_h))
    rounds_np = np.asarray(state.rounds_done)
    codes_np = np.asarray(state.stop_code)

    # ---- per-session views (loop-baseline-compatible) ----------------------
    # Identical pricing to CFLLearner/DFLLearner.run_config, with the
    # analytic t_local_fit fallback (a compiled fleet has no per-node
    # host wall clock to measure); battery=None like the loop baselines.
    num_params = tree_size(template)
    model_bytes = update_wire_bytes(num_params, encrypt=False,
                                    compress=getattr(cfg, "compress", None),
                                    raw_bytes=tree_bytes(template))
    last_p = tree_unravel(ravel_spec, last_flat)
    tl.finish(_sp_unpack)
    fc = getattr(cfg, "faults", None)
    sessions = []
    total_e = 0.0
    for i, spec in enumerate(requesters):
        r_i = int(rounds_np[i])
        n_cli = len(rosters[i])
        if method == "cfl":
            report = cost.cfl_session(
                rounds=r_i, num_params=num_params, model_bytes=model_bytes,
                num_samples=len(spec.own_train[0]), epochs=cfg.epochs)
            history = {"accuracy": [float(a) for a in acc_h[:r_i, i]],
                       "loss": []}
        else:
            report = cost.dfl_session(
                rounds=r_i, n_peers=n_cli - 1, num_params=num_params,
                model_bytes=model_bytes,
                num_samples=len(spec.own_train[0]), epochs=cfg.epochs,
                topology=dfl_topology)
            history = {"accuracy": [float(a) for a in acc_h[:r_i, i]]}
        if fc is not None and r_i:
            # the baselines' loop oracles define convergence, so link
            # faults price in the COST domain only: the same fault world
            # (requester fc.requester_id + i), rolled over this method's
            # wire links — the one server uplink (cfl, WAN-rated) or the
            # gossip fan (dfl) — and every extra transmission re-priced
            # through the one CostModel, same as the enfed engines
            if method == "cfl":
                link_ids = np.array([0], np.int32)
                _, e_tx_r, t_xfer = cost.retry_energy(
                    model_bytes=model_bytes, encrypt=False,
                    rate_bps=cost.link.wan_rate_bps)
            else:
                fan = (n_cli - 1 if dfl_topology == "mesh"
                       else min(2, n_cli - 1))
                link_ids = np.arange(1, fan + 1, dtype=np.int32)
                _, e_tx_r, t_xfer = cost.retry_energy(
                    model_bytes=model_bytes, encrypt=True)
            extra = 0.0
            for r in range(r_i):
                delivered, attempts, _ = faults_mod.link_outcomes(
                    fc, r, fc.requester_id + i, link_ids)
                extra += float(np.sum(np.asarray(attempts))
                               - np.sum(np.asarray(delivered)))
            report.times.t_com += extra * t_xfer
            report.e_comm += extra * e_tx_r
            history["fault_extra_tx"] = extra
        total_e += report.e_tot
        sessions.append(SessionResult(
            accuracy=history["accuracy"][-1] if history["accuracy"] else 0.0,
            rounds=r_i, n_contributors=n_cli - 1, report=report,
            battery=None, history=history,
            stop_reason=protocol.stop_reason_name(codes_np[i]),
            params=jax.tree_util.tree_map(lambda l: l[i], last_p),
            model_bytes=model_bytes))
    return FleetResult(
        sessions=sessions, rounds=rounds_np, stop_codes=codes_np,
        accuracy=np.array([s.accuracy for s in sessions], np.float32),
        battery_level=np.asarray(level), total_energy_j=float(total_e),
        history={"accuracy": acc_h, "loss": loss_h, "battery": bat_h,
                 "executed": exec_h, "round_executed": body_h,
                 "member": member_h},
        staged_host_bytes=staged_bytes, staged_index_bytes=index_bytes,
        staged_shard_bytes=shard_bytes,
        staged_shard_bytes_dense=shard_bytes_dense,
        staged_param_bytes=staged_param_bytes,
        device_round_state_bytes=staged_param_bytes,
        refresh_gather_bytes=0, refresh_gather_bytes_dense=0,
        timeline=tl, hlo_stats=hlo)
