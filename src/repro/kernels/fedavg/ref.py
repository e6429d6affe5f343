"""Pure-jnp oracle for the fused masked-weighted FedAvg reduction.

The contractions ask for full fp32 precision: on a TPU a float32 einsum
otherwise runs as one bf16 pass, and the oracle is a float32 mean."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def fedavg_ref(updates, weights):
    """updates: (N, L) contributor-stacked flat updates; weights: (N,)
    (participation mask x data-size weights). Returns (L,) fp32:

        out = sum_j w_j * u_j / max(sum_j w_j, eps)      (paper eq. 14)
    """
    w = weights.astype(jnp.float32)
    num = jnp.einsum("n,nl->l", w, updates.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    return num / jnp.maximum(jnp.sum(w), 1e-9)


def fedavg_batched_ref(updates, weights):
    """updates: (R, N, L); weights: (R, N). Requester-batched eq. (14):
    one independent masked-weighted mean per leading session index."""
    w = weights.astype(jnp.float32)
    num = jnp.einsum("rn,rnl->rl", w, updates.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    return num / jnp.maximum(jnp.sum(w, axis=1, keepdims=True), 1e-9)


def fedavg_batched_q8_ref(q, scales, weights):
    """q: (R, N, Lp) int8; scales: (R, N, Lp/tile) fp32; weights:
    (R, N).  Dequantize (exact ``q * scale``) then the batched eq. (14)
    — the oracle for the fused dequant->fedavg kernel."""
    r, n, lp = q.shape
    tile = lp // scales.shape[-1]
    u = (q.astype(jnp.float32).reshape(r, n, -1, tile)
         * scales[..., None]).reshape(r, n, lp)
    return fedavg_batched_ref(u, weights)
