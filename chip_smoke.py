#!/usr/bin/env python3
"""Chip smoke test: the EnFed fleet program, end to end, on one TPU.

Drives the main path through its public entry point
(``Experiment(..., ExecutionSpec(engine="fleet")).run()`` ->
``run_fleet`` -> the compiled fleet program) at the paper MLP's full
width: ``repro.configs.PAPER_MLP`` (8-64-32-5, P = 2821 parameters),
R = 512 concurrent requester sessions x N = 5 contributors (the
paper's five), calories data generated from a seed, 3 rounds with a
target accuracy no run can reach, so every phase runs the same rounds.

Eight phases together launch every fleet-path Pallas kernel: fp32
fedavg; int8 (quantize + fused dequant-fedavg); and robust
``trimmed_mean``, ``median`` and ``clip`` (norm + fedavg) on the fp32
and the int8 round state.  ``interpret``, ``use_pallas`` and the matmul
precision stay at their defaults.  Each phase checks, on the chip:

* the phase's aggregation kernels against their jnp references on the
  same random round state at the smoke's shapes, within fp32 rounding
  (``KERNEL_RTOL`` / ``KERNEL_ATOL``; int8 codes within one step);
* the fleet run against the same run with ``use_pallas=False`` (the
  jnp reference aggregation): rounds, stop reasons, membership and clip
  sets of all R sessions identical (the structural fields of the
  RoundEvent stream); final params of all R sessions within the phase's
  bound on the 99.9th percentile of their differences and within
  ``PARAM_MAX`` on the largest;
* that the compiled program holds the phase's Pallas kernels
  (``tpu_custom_call``, as many as the phase launches), so no kernel ran
  interpreted or fell back to its reference;
* in the fedavg and int8 phases, that a second (warm) run is bitwise
  identical to the first.

Earlier lines print, per phase, compile seconds (the AOT lower+compile
of ``TraceConfig.hlo_stats``), warm seconds of the fleet program to
``block_until_ready`` (the ``program`` span), and the device's peak
memory.  They are information from one run, not a benchmark.  The last
line of stdout is ``{"ok": true, "device": {...}}``.

    python chip_smoke.py

Exits non-zero, printing no result, where JAX finds no TPU.  Runs in
one process and starts none.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

R = 512            # concurrent requester sessions
N = 5              # contributors per requester (the paper's five VMs)
MAX_ROUNDS = 3
BATCH = 32
OWN_SAMPLES = 4 * BATCH    # each requester's private training shard
SEED = 0

# The kernels compute what their references compute, in another order:
# fp32 rounding is all that may differ.
KERNEL_RTOL = 1e-5
KERNEL_ATOL = 1e-6
# End to end, kernel and reference aggregates differ in their last
# ulps, and each round's requester fit (Adam from a fresh state, lr 3e-3,
# at the TPU's default matmul precision) carries that on: a coordinate
# whose gradient is near zero can step in opposite directions in the two
# runs, so the bulk of the params stays close while a few move far.  The
# bounds were set from one reading of each phase on a v5e (PERF.md,
# section 6), with room above it; on the CPU, a 1e-4 relative error in
# every aggregate reads 9e-4 (clip) to 1.2e-3 (fedavg) at the 99.9th
# percentile.  The largest difference read 8.9e-3 (clip); its bound
# catches a defect confined to a few coordinates, which moves them on
# the params' own scale (median |param| about 0.05).
PARAM_MAX = 5e-2
# A warm rerun costs about 30 s of host staging and unpacking at R = 512;
# it runs in the two plain aggregation phases, which keeps the script
# well inside 20 minutes.
WARM_PHASES = ("fedavg", "int8")

# (label, compress, robust, Pallas kernels in the program, bound on the
# 99.9th percentile of |param difference|).  The median kernels select
# their inputs and take 0.5 * (a + b) as the reference does, so their
# params are exact.
PHASES = (
    ("fedavg", None, "none", 1, 1e-4),          # read 2.3e-5
    ("int8", "int8", "none", 2, 1e-4),          # read 1.8e-5
    ("trimmed_mean", None, "trimmed_mean", 1, 5e-5),     # read 0
    ("trimmed_mean_q8", "int8", "trimmed_mean", 2, 5e-5),  # read 8.3e-6
    ("median", None, "median", 1, 0.0),
    ("median_q8", "int8", "median", 2, 0.0),
    ("clip", None, "clip", 2, 3e-4),            # read 1.1e-4
    ("clip_q8", "int8", "clip", 3, 3e-4),       # read 1.4e-4
)
# The kernels each phase launches: fedavg_batched; quantize_batched +
# fedavg_batched_q8; the robust statistic, alone or on the int8 wire
# after quantize_batched; clip's sqnorm + fedavg_batched, or quantize +
# sqnorm_q8 + fedavg_batched_q8.

# RoundEvent fields that must match exactly between kernel and reference
STRUCTURAL = ("round", "requester", "phase", "executed", "members",
              "member_set", "delivered", "stop_reason", "clipped")


def build_world(r: int = R, seed: int = SEED):
    """The paper MLP task, N pre-trained contributors and ``r``
    requesters, each with its own shard, all from ``seed``."""
    from repro.api import WorldSpec
    from repro.configs import PAPER_MLP
    from repro.core import RequesterSpec, SupervisedTask, make_fleet
    from repro.data import (CaloriesDatasetConfig, dirichlet_partition,
                            make_calories_tabular)
    from repro.models import MLPClassifier

    x, y = make_calories_tabular(CaloriesDatasetConfig(seed=seed))
    task = SupervisedTask(MLPClassifier(PAPER_MLP), lr=3e-3)
    parts = dirichlet_partition(y, num_clients=N + 1, alpha=100.0, seed=seed)
    shards = [(x[p], y[p]) for p in parts]
    fleet = make_fleet(N, seed=seed + 1, p_has_model=1.0)
    states = {}
    for i, dev in enumerate(fleet):
        dev.reservation_price = 0.4
        params, _ = task.fit(task.init(seed=10 + i), shards[i + 1], epochs=1,
                             batch_size=BATCH, seed=i)
        states[dev.device_id] = {"params": params, "data": shards[i + 1]}
    own_x, own_y = shards[0]
    n_train = int(len(own_x) * 0.8)
    own_test = (own_x[n_train:], own_y[n_train:])
    rng = np.random.default_rng(seed)
    requesters = []
    for _ in range(r):
        sel = rng.permutation(n_train)[:OWN_SAMPLES]
        requesters.append(RequesterSpec(
            own_train=(own_x[sel], own_y[sel]), own_test=own_test,
            neighborhood=fleet, contributor_states=states))
    return WorldSpec(task=task, requesters=requesters, seed=seed)


def _method(compress, robust):
    from repro.api import MethodSpec
    return MethodSpec(desired_accuracy=1.01, max_rounds=MAX_ROUNDS,
                      batch_size=BATCH, n_max=N, compress=compress,
                      robust=robust)


def _flat_params(result) -> np.ndarray:
    """(R, P) final params of every session, in ``ravel_pytree`` order,
    copied to the host in one batch."""
    import jax
    trees = jax.device_get([s.params for s in result.sessions])
    return np.stack([np.concatenate([np.ravel(leaf) for leaf in
                                     jax.tree_util.tree_leaves(t)])
                     for t in trees])


def _structure(result):
    return [tuple(getattr(e, f) for f in STRUCTURAL) for e in result.trace]


def check_kernels(compress, robust, p: int, seed: int = SEED) -> float:
    """The phase's aggregation kernels, Pallas against reference, on one
    random round state of shape (R, N, p) (int8: its wire image).
    Returns the largest absolute difference; raises past the bounds."""
    import jax.numpy as jnp
    from repro.kernels.fedavg.ops import (fedavg_flat_batched,
                                          fedavg_flat_batched_q8)
    from repro.kernels.quantize.ops import padded_len, quantize_flat_batched
    from repro.kernels.robust.ops import robust_aggregate, robust_aggregate_q8

    rng = np.random.default_rng(seed)
    u = jnp.asarray(rng.normal(0.0, 0.1, (R, N, p)).astype(np.float32))
    w = jnp.asarray(((rng.random((R, N)) > 0.2)
                     * rng.random((R, N))).astype(np.float32))
    if compress == "int8":
        lp = padded_len(p)
        rows = jnp.pad(u, ((0, 0), (0, 0), (0, lp - p))).reshape(R * N, lp)
        qk, sk = quantize_flat_batched(rows)
        q, s = quantize_flat_batched(rows, use_pallas=False)
        steps = int(np.max(np.abs(np.asarray(qk, np.int32)
                                  - np.asarray(q, np.int32))))
        if steps > 1 or not np.allclose(sk, s, rtol=KERNEL_RTOL, atol=0):
            raise AssertionError(f"quantize: codes off by {steps} or "
                                 "scales differ from reference")
        args = (q.reshape(R, N, lp), s.reshape(R, N, -1), w)
        plain, robust_fn = fedavg_flat_batched_q8, robust_aggregate_q8
    else:
        args = (u, w)
        plain, robust_fn = fedavg_flat_batched, robust_aggregate
    if robust == "none":
        got, want = plain(*args), plain(*args, use_pallas=False)
    else:
        (got, gclip), (want, wclip) = (
            robust_fn(*args, method=robust),
            robust_fn(*args, method=robust, use_pallas=False))
        if not np.array_equal(gclip, wclip):
            raise AssertionError(f"{robust}: clip masks differ")
    got, want = np.asarray(got), np.asarray(want)
    diff = float(np.max(np.abs(got - want)))
    if not np.allclose(got, want, rtol=KERNEL_RTOL, atol=KERNEL_ATOL):
        raise AssertionError(f"{robust}/{compress}: kernel differs from "
                             f"reference by {diff}")
    return diff


def run_phase(world, label, compress, robust, n_kernels, p999_bound,
              device) -> dict:
    """One phase: the kernel check, a traced Pallas run (compiles), a
    warm Pallas run (``WARM_PHASES``) and the reference run, checked
    against each other.  Raises on any mismatch."""
    import jax
    from repro.api import ExecutionSpec, Experiment
    from repro.telemetry import TraceConfig

    t0 = time.perf_counter()
    method = _method(compress, robust)
    first = Experiment(world, method, ExecutionSpec(
        engine="fleet", trace=TraceConfig(hlo_stats=True))).run()
    warm = (Experiment(world, method, ExecutionSpec(engine="fleet")).run()
            if label in WARM_PHASES else None)
    ref = Experiment(world, method,
                     ExecutionSpec(engine="fleet", use_pallas=False)).run()
    got, want = _flat_params(first), _flat_params(ref)
    kernel_diff = check_kernels(compress, robust, got.shape[1])

    kernels = int(first.hlo_stats.get("tpu_custom_calls", 0))
    if kernels != n_kernels:
        raise AssertionError(f"{label}: {kernels} tpu_custom_call in the "
                             f"program, expected {n_kernels}")
    if not np.all(np.isfinite(got)):
        raise AssertionError(f"{label}: non-finite params")
    if warm is not None and not np.array_equal(got, _flat_params(warm)):
        raise AssertionError(f"{label}: warm run differs from the first")
    if [s.rounds for s in first.sessions] != [s.rounds for s in ref.sessions]:
        raise AssertionError(f"{label}: round counts differ from reference")
    if ([s.stop_reason for s in first.sessions]
            != [s.stop_reason for s in ref.sessions]):
        raise AssertionError(f"{label}: stop reasons differ from reference")
    if _structure(first) != _structure(ref):
        raise AssertionError(f"{label}: membership differs from reference")
    diff = np.abs(got - want)
    p999, dmax = float(np.quantile(diff, 0.999)), float(diff.max())
    if p999 > p999_bound or dmax > PARAM_MAX:
        raise AssertionError(
            f"{label}: params differ from reference: 99.9th percentile "
            f"{p999} (bound {p999_bound}), max {dmax} (bound {PARAM_MAX})")
    stats = device.memory_stats() or {}
    row = {
        "phase": label, "rounds": first.rounds,
        "stop_reason": first.stop_reason, "tpu_custom_calls": kernels,
        "kernel_diff_max": kernel_diff,
        "compile_s": first.timings.get("hlo_stats"),
        "first_spans_s": first.timings,
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "param_diff_p999": p999, "param_diff_max": dmax,
        "accuracy": first.accuracy, "reference_accuracy": ref.accuracy,
    }
    if warm is not None:
        row.update(warm_program_s=warm.timings.get("program"),
                   warm_run_s=warm.wall_s, warm_spans_s=warm.timings)
    row["phase_s"] = time.perf_counter() - t0
    return row


def main() -> int:
    import jax

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {device.platform!r}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    from repro.utils.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    t0 = time.perf_counter()
    world = build_world()
    print(json.dumps({"setup_s": time.perf_counter() - t0, "R": R, "N": N,
                      "max_rounds": MAX_ROUNDS}), flush=True)
    for phase in PHASES:
        row = run_phase(world, *phase, device)
        print(json.dumps(row), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
