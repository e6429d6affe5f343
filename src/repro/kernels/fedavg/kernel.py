"""Pallas TPU kernel: fused masked-weighted FedAvg aggregation.

Aggregation (paper eq. 14) is a memory-bound reduction over the
contributor axis: for every parameter tile we stream N contributor
slices HBM -> VMEM once and emit one fp32 tile.  Fusing the mask, the
weighting, and the normalization into one pass avoids materializing the
masked intermediate (which a naive ``(mask*w)[:,None]*updates`` would
write back to HBM at full N x L size).

Tiling: grid (R/TR, L/TILE_L), block (TR, N, TILE_L) with TILE_L = 2048
(16 x 128 lanes).  TR requester sessions share a grid step; it is sized
by ``repro.kernels.common.tile_rows`` so the block's fp32 VMEM slab
(N padded to 8 sublanes) stays near 2 MB, and it is the whole R or a
multiple of 8, which the (TR, N) weight and (TR, TILE_L) output blocks
need.  The single-session :func:`fedavg_pallas` is the R = 1 case.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import resolve_interpret, slab_bytes, tile_rows
from repro.kernels.quantize.kernel import TILE as Q_TILE
from repro.kernels.quantize.kernel import tile_scale

TILE_L = 2048


def _weighted_sum(w, u):
    """sum_n w[r, n] * u[r, n, :] -> (TR, TILE) as a batched matmul with
    a unit row dim (Mosaic has no batched dot without one), at fp32
    precision on the TPU's MXU."""
    return jnp.einsum("rmn,rnl->rml", w[:, None, :], u,
                      precision=jax.lax.Precision.HIGHEST)[:, 0, :]


def _fedavg_batched_kernel(w_ref, u_ref, o_ref):
    """w_ref: (TR, N) fp32; u_ref: (TR, N, TILE_L); o_ref: (TR, TILE_L).

    A TILE of requester sessions per leading grid step — the fleet
    engine's aggregation hot path runs every session's eq. (14) in one
    launch.  Tiling R (instead of one session per step) keeps the grid
    small: interpret mode (the CPU path) walks grid steps serially with
    per-step overhead, so a (R, L/TILE_L) grid turned the aggregation
    into the R=512 scaling cliff; (R/TR, L/TILE_L) removes it while the
    (TR, N, TILE_L) block stays VMEM-bounded on TPU.
    """
    w = w_ref[...]
    u = u_ref[...].astype(jnp.float32)
    num = _weighted_sum(w, u)
    denom = jnp.maximum(jnp.sum(w, axis=1, keepdims=True), 1e-9)
    o_ref[...] = num / denom


def _fedavg_batched_q8_kernel(w_ref, q_ref, s_ref, o_ref):
    """w_ref: (TR, N) fp32; q_ref: (TR, N, Q_TILE) int8; s_ref:
    (TR, N, Lp/Q_TILE) fp32, every tile's scale (this step's column is
    picked by ``tile_scale``); o_ref: (TR, Q_TILE) fp32.

    The compressed round state's hot path: dequantize every
    contributor's int8 tile (``q * scale``, the exact wire inverse) and
    reduce it into the masked weighted mean in ONE pass through VMEM —
    the (N, Q_TILE) fp32 intermediate a separate dequant would write
    back to HBM at full round-state size never exists.  R is tiled like
    :func:`_fedavg_batched_kernel` to keep the grid small.
    """
    w = w_ref[...]
    u = q_ref[...].astype(jnp.float32) * tile_scale(s_ref[...])
    num = _weighted_sum(w, u)
    denom = jnp.maximum(jnp.sum(w, axis=1, keepdims=True), 1e-9)
    o_ref[...] = num / denom


@functools.partial(jax.jit, static_argnames=("interpret",))
def fedavg_batched_q8_pallas(q, scales, weights, *, interpret=None):
    """q: (R, N, Lp) int8 wire payload, Lp % Q_TILE == 0; scales:
    (R, N, Lp/Q_TILE) fp32; weights: (R, N).  Returns (R, Lp) fp32.

    The q8 counterpart of :func:`fedavg_batched_pallas`: grid
    (R/TR, Lp/Q_TILE) — one quantization tile per trailing grid step, so
    each step dequantizes with one scale per contributor — with the
    dequant fused into the reduction.  Used by ``repro.core.fleet``
    under ``EnFedConfig.compress="int8"`` to aggregate every concurrent
    session straight from the compressed round-state buffer.
    """
    interpret = resolve_interpret(interpret)
    r, n, lp = q.shape
    if lp % Q_TILE:
        raise ValueError(f"fedavg_batched_q8 needs Lp % {Q_TILE} == 0 "
                         f"(got {lp}); the wire format is tile-padded")
    tr = tile_rows(r, slab_bytes(n, Q_TILE))
    pad_r = (-r) % tr
    if pad_r:
        q = jnp.pad(q, ((0, pad_r), (0, 0), (0, 0)))
        scales = jnp.pad(scales, ((0, pad_r), (0, 0), (0, 0)))
        weights = jnp.pad(weights, ((0, pad_r), (0, 0)))
    grid = ((r + pad_r) // tr, lp // Q_TILE)
    out = pl.pallas_call(
        _fedavg_batched_q8_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tr, n), lambda i, j: (i, 0)),
            pl.BlockSpec((tr, n, Q_TILE), lambda i, j: (i, 0, j)),
            pl.BlockSpec((tr, n, lp // Q_TILE), lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((tr, Q_TILE), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((r + pad_r, lp), jnp.float32),
        interpret=interpret,
    )(weights.astype(jnp.float32), q, scales)
    return out[:r]


@functools.partial(jax.jit, static_argnames=("interpret",))
def fedavg_batched_pallas(updates, weights, *, interpret=None):
    """updates: (R, N, L); weights: (R, N). Returns (R, L) fp32.

    The requester-batched form of :func:`fedavg_pallas`: grid
    (R/TR, L/TILE_L), each step reduces a TILE of requesters' contributor
    stacks for one parameter tile.  Used by ``repro.core.fleet`` to
    aggregate every concurrent session in a single kernel launch.
    """
    interpret = resolve_interpret(interpret)
    r, n, l = updates.shape
    pad = (-l) % TILE_L
    if pad:
        updates = jnp.pad(updates, ((0, 0), (0, 0), (0, pad)))
    lp = l + pad
    tr = tile_rows(r, slab_bytes(n, TILE_L))
    pad_r = (-r) % tr
    if pad_r:
        updates = jnp.pad(updates, ((0, pad_r), (0, 0), (0, 0)))
        weights = jnp.pad(weights, ((0, pad_r), (0, 0)))
    grid = ((r + pad_r) // tr, lp // TILE_L)
    out = pl.pallas_call(
        _fedavg_batched_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tr, n), lambda i, j: (i, 0)),
            pl.BlockSpec((tr, n, TILE_L), lambda i, j: (i, 0, j)),
        ],
        out_specs=pl.BlockSpec((tr, TILE_L), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((r + pad_r, lp), jnp.float32),
        interpret=interpret,
    )(weights.astype(jnp.float32), updates)
    return out[:r, :l]


@functools.partial(jax.jit, static_argnames=("interpret",))
def fedavg_pallas(updates, weights, *, interpret=None):
    """updates: (N, L); weights: (N,). Returns (L,) fp32 — the batched
    kernel on one session.  L is padded to a TILE_L multiple internally;
    callers pass any L."""
    return fedavg_batched_pallas(updates[None], weights[None],
                                 interpret=interpret)[0]
