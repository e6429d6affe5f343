"""Pallas TPU kernels: Byzantine-robust aggregation statistics over the
wire-format round-state buffer.

Three robust statistics replace the plain masked-weighted mean of
``repro.kernels.fedavg`` when ``EnFedConfig.robust != "none"``:

* **trimmed mean** — per coordinate, the single largest and single
  smallest active contribution (first instance on value ties) are
  dropped and the weighted mean runs over the rest; with <= 2 active
  contributors it degrades to the plain weighted mean.
* **median** — per coordinate, the middle active value (mean of the two
  middles for even counts); weights gate activity only.
* **per-contributor squared L2 norm** — the reduction feeding norm-clip
  screening (``repro.kernels.robust.ops.robust_aggregate``'s "clip"
  path): norms accumulate tile by tile into an (R, N) output block that
  the grid revisits, so the full fp32 vector never round-trips HBM.

Every statistic ships a ``*_q8`` twin that fuses the int8 dequant
(``q * scale``, the exact wire inverse) into the same VMEM pass — the
compressed (R, N, P) round state is screened WITHOUT materializing the
dense fp32 block (the never-re-densify rule), exactly like
``fedavg_batched_q8``.  The q8 kernels dequantize first and then run
bit-identical arithmetic to the dense kernels, so the loop engine
(dense dequantized payloads) and the fleet engine (fused q8 buffer)
agree bitwise on every order statistic and clip decision.

Tiling matches ``repro.kernels.fedavg.kernel``: grid
(R/TR, L/TILE), block (TR, N, TILE), requester tile from
``repro.kernels.common.tile_rows``; the q8 twins read every tile's
scale in one full-width block and pick their column with
``repro.kernels.quantize.kernel.tile_scale``.  The contributor axis N
is small (n_max-bounded), so the per-coordinate order statistics run as
a static odd-even transposition network / one-hot selections along
axis 1 — no dynamic gather, Pallas-lowerable on TPU.
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


def _dequant(q, s):
    """Exact wire inverse ``q * scale`` for one (TR, N, TILE) block, the
    scale picked from the (TR, N, Lp/TILE) block of every tile's."""
    return q.astype(jnp.float32) * tile_scale(s)


# ---------------------------------------------------------------------------
# trimmed mean
# ---------------------------------------------------------------------------


def _trimmed_mean_block(w, u):
    """w: (TR, N) fp32; u: (TR, N, T) fp32 -> (TR, T) fp32.

    Per-coordinate weighted trimmed mean: drop the max and the min
    ACTIVE instance (first index on ties — the same instance the ref's
    argmax/argmin picks), weighted-average the rest; <= 2 active
    contributors fall back to the plain weighted mean; 0 active -> 0
    (the fedavg all-masked convention).
    """
    u = u.astype(jnp.float32)
    n = u.shape[1]
    w3 = w[:, :, None]                               # (TR, N, 1)
    act = w3 > 0.0
    wb = jnp.where(act, w3, 0.0)
    m3 = jnp.sum(act.astype(jnp.int32), axis=1, keepdims=True)  # (TR, 1, 1)
    n_idx = jax.lax.broadcasted_iota(jnp.int32, u.shape, 1)
    vmax_in = jnp.where(act, u, -jnp.inf)
    vmax = jnp.max(vmax_in, axis=1, keepdims=True)
    is_max = act & (vmax_in == vmax)
    amax = jnp.min(jnp.where(is_max, n_idx, jnp.int32(n)), axis=1,
                   keepdims=True)
    one_max = n_idx == amax
    vmin_in = jnp.where(act & ~one_max, u, jnp.inf)
    vmin = jnp.min(vmin_in, axis=1, keepdims=True)
    is_min = (act & ~one_max) & (vmin_in == vmin)
    amin = jnp.min(jnp.where(is_min, n_idx, jnp.int32(n)), axis=1,
                   keepdims=True)
    one_min = n_idx == amin
    w_eff = jnp.where(one_max | one_min, 0.0, wb)
    w_use = jnp.where(m3 > 2, w_eff, wb)
    num = jnp.sum(w_use * jnp.where(act, u, 0.0), axis=1)
    den = jnp.maximum(jnp.sum(w_use, axis=1), 1e-9)
    return num / den


def _trimmed_mean_batched_kernel(w_ref, u_ref, o_ref):
    o_ref[...] = _trimmed_mean_block(w_ref[...], u_ref[...])


def _trimmed_mean_batched_q8_kernel(w_ref, q_ref, s_ref, o_ref):
    o_ref[...] = _trimmed_mean_block(w_ref[...], _dequant(q_ref[...],
                                                          s_ref[...]))


# ---------------------------------------------------------------------------
# median
# ---------------------------------------------------------------------------


def _sorted_rows(v, n: int):
    """Odd-even transposition sort along axis 1 (static N passes) — the
    sorted VALUES match ``jnp.sort(v, axis=1)`` exactly; the network is
    comparison/select only, hence Pallas-lowerable."""
    rows = [v[:, j, :] for j in range(n)]
    for phase in range(n):
        for j in range(phase % 2, n - 1, 2):
            a, b = rows[j], rows[j + 1]
            rows[j], rows[j + 1] = jnp.minimum(a, b), jnp.maximum(a, b)
    return rows


def _median_block(w, u):
    """w: (TR, N) fp32; u: (TR, N, T) fp32 -> (TR, T) fp32.

    Per-coordinate masked median over the active contributors (weights
    gate activity only); 0 active -> 0.
    """
    u = u.astype(jnp.float32)
    n = u.shape[1]
    act = w[:, :, None] > 0.0
    m = jnp.sum(act.astype(jnp.int32), axis=1)          # (TR, 1)
    rows = _sorted_rows(jnp.where(act, u, jnp.inf), n)
    lo = jnp.maximum((m - 1) // 2, 0)
    hi = jnp.maximum(m // 2, 0)
    vlo = rows[0] * 0.0
    vhi = rows[0] * 0.0
    for j in range(n):
        vlo = jnp.where(lo == j, rows[j], vlo)
        vhi = jnp.where(hi == j, rows[j], vhi)
    med = 0.5 * (vlo + vhi)
    return jnp.where(m > 0, med, 0.0)


def _median_batched_kernel(w_ref, u_ref, o_ref):
    o_ref[...] = _median_block(w_ref[...], u_ref[...])


def _median_batched_q8_kernel(w_ref, q_ref, s_ref, o_ref):
    o_ref[...] = _median_block(w_ref[...], _dequant(q_ref[...], s_ref[...]))


# ---------------------------------------------------------------------------
# per-contributor squared L2 norm (clip screening)
# ---------------------------------------------------------------------------


def _sqnorm_batched_kernel(u_ref, o_ref):
    """u_ref: (TR, N, TILE) -> accumulate sum(u^2) over the L grid axis
    into o_ref (TR, N).  The output block is revisited across the
    trailing grid dimension (sequential on TPU), initialized at j == 0."""
    j = pl.program_id(1)
    u = u_ref[...].astype(jnp.float32)
    part = jnp.sum(u * u, axis=2)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = part

    @pl.when(j > 0)
    def _acc():
        o_ref[...] += part


def _sqnorm_batched_q8_kernel(q_ref, s_ref, o_ref):
    j = pl.program_id(1)
    u = _dequant(q_ref[...], s_ref[...])
    part = jnp.sum(u * u, axis=2)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = part

    @pl.when(j > 0)
    def _acc():
        o_ref[...] += part


# ---------------------------------------------------------------------------
# launch wrappers
# ---------------------------------------------------------------------------


def _launch_dense(kernel, updates, weights, interpret):
    """Shared (R, N, L) launch: pad L to TILE_L, tile R, slice back."""
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
        kernel,
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


def _launch_q8(kernel, q, scales, weights, interpret):
    """Shared (R, N, Lp) int8 launch: one Q_TILE per trailing grid step,
    so each step dequantizes with one scale per contributor."""
    r, n, lp = q.shape
    if lp % Q_TILE:
        raise ValueError(f"robust q8 kernels need Lp % {Q_TILE} == 0 "
                         f"(got {lp}); the wire format is tile-padded")
    tr = tile_rows(r, slab_bytes(n, Q_TILE))
    pad_r = (-r) % tr
    if pad_r:
        q = jnp.pad(q, ((0, pad_r), (0, 0), (0, 0)))
        scales = jnp.pad(scales, ((0, pad_r), (0, 0), (0, 0)))
        weights = jnp.pad(weights, ((0, pad_r), (0, 0)))
    grid = ((r + pad_r) // tr, lp // Q_TILE)
    out = pl.pallas_call(
        kernel,
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
def trimmed_mean_batched_pallas(updates, weights, *, interpret=None):
    """updates: (R, N, L); weights: (R, N). Returns (R, L) fp32."""
    return _launch_dense(_trimmed_mean_batched_kernel, updates, weights,
                         resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def trimmed_mean_batched_q8_pallas(q, scales, weights, *, interpret=None):
    """q: (R, N, Lp) int8; scales: (R, N, Lp/Q_TILE); weights: (R, N).
    Returns (R, Lp) fp32 — dequant fused, fp32 block never materialized."""
    return _launch_q8(_trimmed_mean_batched_q8_kernel, q, scales, weights,
                      resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def median_batched_pallas(updates, weights, *, interpret=None):
    """updates: (R, N, L); weights: (R, N). Returns (R, L) fp32."""
    return _launch_dense(_median_batched_kernel, updates, weights,
                         resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def median_batched_q8_pallas(q, scales, weights, *, interpret=None):
    """q: (R, N, Lp) int8; scales: (R, N, Lp/Q_TILE); weights: (R, N).
    Returns (R, Lp) fp32 — dequant fused."""
    return _launch_q8(_median_batched_q8_kernel, q, scales, weights,
                      resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def sqnorm_batched_pallas(updates, *, interpret=None):
    """updates: (R, N, L) -> (R, N) fp32 squared L2 norms, accumulated
    tile-by-tile (the clip screening reduction)."""
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
    grid = ((r + pad_r) // tr, lp // TILE_L)
    out = pl.pallas_call(
        _sqnorm_batched_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((tr, n, TILE_L), lambda i, j: (i, 0, j))],
        out_specs=pl.BlockSpec((tr, n), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r + pad_r, n), jnp.float32),
        interpret=interpret,
    )(updates)
    return out[:r]


@functools.partial(jax.jit, static_argnames=("interpret",))
def sqnorm_batched_q8_pallas(q, scales, *, interpret=None):
    """q: (R, N, Lp) int8; scales: (R, N, Lp/Q_TILE) -> (R, N) fp32
    squared norms straight off the wire-format buffer (dequant fused)."""
    interpret = resolve_interpret(interpret)
    r, n, lp = q.shape
    if lp % Q_TILE:
        raise ValueError(f"robust q8 kernels need Lp % {Q_TILE} == 0 "
                         f"(got {lp}); the wire format is tile-padded")
    tr = tile_rows(r, slab_bytes(n, Q_TILE))
    pad_r = (-r) % tr
    if pad_r:
        q = jnp.pad(q, ((0, pad_r), (0, 0), (0, 0)))
        scales = jnp.pad(scales, ((0, pad_r), (0, 0), (0, 0)))
    grid = ((r + pad_r) // tr, lp // Q_TILE)
    out = pl.pallas_call(
        _sqnorm_batched_q8_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tr, n, Q_TILE), lambda i, j: (i, 0, j)),
            pl.BlockSpec((tr, n, lp // Q_TILE), lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((tr, n), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r + pad_r, n), jnp.float32),
        interpret=interpret,
    )(q, scales)
    return out[:r]
