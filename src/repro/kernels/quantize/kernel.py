"""Pallas TPU kernel: per-tile symmetric int8 quantize / dequantize.

Beyond-paper optimization: the paper cites gradient/weight quantization
as the standard lever for communication energy ([13], [14]) but does not
use it.  EnFed's update transport is the dominant communication cost
(R x N_c x w bytes), so int8-compressing the update stream cuts both the
radio energy of the fleet simulation and the collective bytes of the
distributed roofline by ~4x.

One fused pass: absmax reduction and scaled round-to-int8 in VMEM, one
tile per grid step, scale emitted per tile.  Dequant is the inverse pass
fused into the receive path.

Layout: every kernel works on 2-D ``(rows, TILE)`` blocks.  Per-tile
scales travel as a ``(rows, Lp/TILE)`` array whose block spans the full
last dim (Mosaic refuses a ``(rows, 1)`` block over a wider array); a
grid step picks or writes its own column with an exact one-hot select
(:func:`tile_scale`).  The 1-D ``quantize_pallas`` / ``dequantize_pallas``
view an update as ``(Lp/TILE, TILE)`` rows, one tile per row.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import resolve_interpret, tile_rows

TILE = 1024


def tile_scale(s):
    """``s[..., j:j+1]`` for this grid step's tile ``j = program_id(1)``,
    from a block ``s`` whose last dim holds every tile's scale.

    A max over a one-hot mask (the other entries become -inf) — exact,
    and lane-dense, unlike a dynamic slice of width 1.
    """
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, s.ndim - 1)
    return jnp.max(jnp.where(col == pl.program_id(1), s, -jnp.inf),
                   axis=-1, keepdims=True)


def _quant_batched_kernel(x_ref, q_ref, s_ref):
    """x_ref: (TB, TILE) fp32; q_ref: (TB, TILE) int8; s_ref: (TB,
    Lp/TILE) fp32, revisited across the tile axis of the grid.

    A TILE of (batch row, tile) pairs per grid step — the batched form
    the fleet engine's Phase.REFRESH uses to requantize every lane's
    freshly-trained params back into the int8 round state in one launch.
    The absmax reduction stays within a row, so a batched row reproduces
    the 1-D :func:`quantize_pallas` of that row: bit-equal int8 codes,
    scales within 1 ulp of codegen.  Rows are tiled (TB per step) to
    keep the grid small — interpret mode walks grid steps serially.
    """
    x = x_ref[...].astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    scale = jnp.maximum(absmax, 1e-12) / 127.0
    q_ref[...] = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    # every step of the tile axis writes its own column, so the block
    # is complete when the grid moves to the next rows
    col = jax.lax.broadcasted_iota(jnp.int32, s_ref.shape, 1)
    s_ref[...] = jnp.where(col == pl.program_id(1), scale, s_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def quantize_batched_pallas(x, *, interpret=None):
    """x: (B, Lp) fp32 with Lp % TILE == 0 (wire-format rows are padded
    by construction) -> (q int8 (B, Lp), scales fp32 (B, Lp/TILE))."""
    interpret = resolve_interpret(interpret)
    b, lp = x.shape
    if lp % TILE:
        raise ValueError(f"quantize_batched_pallas needs Lp % {TILE} == 0 "
                         f"(got {lp}); pad the wire buffer first")
    tb = tile_rows(b, TILE * 4)
    pad_b = (-b) % tb
    if pad_b:
        x = jnp.pad(x, ((0, pad_b), (0, 0)))
    nq = lp // TILE
    grid = ((b + pad_b) // tb, nq)
    q, s = pl.pallas_call(
        _quant_batched_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((tb, TILE), lambda i, j: (i, j))],
        out_specs=[
            pl.BlockSpec((tb, TILE), lambda i, j: (i, j)),
            pl.BlockSpec((tb, nq), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b + pad_b, lp), jnp.int8),
            jax.ShapeDtypeStruct((b + pad_b, nq), jnp.float32),
        ],
        interpret=interpret,
    )(x)
    return q[:b], s[:b]


@functools.partial(jax.jit, static_argnames=("interpret",))
def quantize_pallas(x, *, interpret=None):
    """x: (L,) fp32 -> (q int8 (Lp,), scales (Lp/TILE,)). Pads to TILE."""
    pad = (-x.shape[0]) % TILE
    if pad:
        x = jnp.pad(x, (0, pad))
    q, s = quantize_batched_pallas(x.reshape(-1, TILE), interpret=interpret)
    return q.reshape(-1), s.reshape(-1)


def _dequant_kernel(q_ref, s_ref, x_ref):
    """q_ref: (TB, TILE) int8; s_ref: (TB, 1) fp32; x_ref: (TB, TILE)."""
    x_ref[...] = q_ref[...].astype(jnp.float32) * s_ref[...]


@functools.partial(jax.jit, static_argnames=("orig_len", "interpret"))
def dequantize_pallas(q, scales, orig_len: int, *, interpret=None):
    """q: (Lp,) int8; scales: (Lp/TILE,) -> (orig_len,) fp32."""
    interpret = resolve_interpret(interpret)
    nq = q.shape[0] // TILE
    tb = tile_rows(nq, TILE * 4)
    pad_b = (-nq) % tb
    q2 = jnp.pad(q.reshape(nq, TILE), ((0, pad_b), (0, 0)))
    s2 = jnp.pad(scales.reshape(nq, 1), ((0, pad_b), (0, 0)))
    x = pl.pallas_call(
        _dequant_kernel,
        grid=((nq + pad_b) // tb,),
        in_specs=[
            pl.BlockSpec((tb, TILE), lambda i: (i, 0)),
            pl.BlockSpec((tb, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((tb, TILE), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nq + pad_b, TILE), jnp.float32),
        interpret=interpret,
    )(q2, s2)
    return x.reshape(-1)[:orig_len]
