"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state — the dry-run
driver sets XLA_FLAGS before any jax import to get 512 placeholder host
devices; tests and benches import this module freely and see 1 device.

Single pod:  (16, 16)    axes ("data", "model")   = 256 chips (v5e pod)
Multi-pod:   (2, 16, 16) axes ("pod", "data", "model") = 512 chips

The ``pod`` axis is the EnFed cross-silo client axis for fsdp configs;
``data`` doubles as the client axis for everything else (DESIGN.md §5).
"""

from __future__ import annotations

import numpy as np

import jax
from jax.sharding import AxisType, Mesh


def _mesh(shape, axes):
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices but only {len(devices)} present; "
            "the dry-run driver must set XLA_FLAGS=--xla_force_host_platform_"
            "device_count=512 before any jax import")
    devs = np.asarray(devices[:n]).reshape(shape)
    return Mesh(devs, axes, axis_types=(AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_debug_mesh(*, multi_pod: bool = False):
    """Small mesh for CPU integration tests (needs 8 fake host devices)."""
    shape = (2, 2, 2) if multi_pod else (4, 2)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def client_axes_for(cfg, mesh) -> tuple:
    """Which mesh axes act as the EnFed/FL client axes for this config.

    fsdp configs consume the data axis for ZeRO sharding, so they
    federate over the pod axis only (cross-silo); everything else
    federates over (pod,) data.

    fsdp + MoE cannot federate at all in THIS environment:
    the token-local MoE dispatch nested inside a client shard_map trips
    three distinct XLA-CPU SPMD-partitioner CHECK-failures (bisected in
    EXPERIMENTS.md §Dry-run).  It trains as conventional sync DP across
    pods instead; on a real TPU backend the pod-level schedule is the
    same one an fsdp dense config exercises successfully.
    """
    names = mesh.axis_names
    if getattr(cfg, "fsdp", False):
        if getattr(cfg, "moe", None) is not None:
            return ()
        return ("pod",) if "pod" in names else ()
    axes = [a for a in ("pod", "data") if a in names]
    return tuple(axes)
