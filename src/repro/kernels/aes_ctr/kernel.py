"""Pallas TPU kernel: AES-128-CTR keystream generation + payload XOR.

The paper AES-encrypts every model update before transmission; at fleet
scale (R rounds x N_c contributors x w bytes) the cipher is a real
per-byte hot loop.  CTR mode is embarrassingly parallel over 16-byte
blocks, so the kernel computes the keystream for a tile of counter
blocks and XORs the payload in the same VMEM pass — the keystream never
touches HBM.

TPU status: SubBytes and the GF(2^8) column multiplies are byte-table
lookups, written as vectorized ``jnp.take`` on tables passed into VMEM.
Mosaic refuses that gather ("Only 2D gather is supported"), so this
kernel does NOT compile for a TPU; a bitsliced formulation would be
needed.  It runs in interpret mode only, validated against the
FIPS-197-checked reference, and neither engine launches it (the loop
engine's transport uses ``repro.core.crypto``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.common import resolve_interpret

from repro.core import crypto

BLOCK_TILE = 512  # AES blocks per grid step (512 x 16 B = 8 KiB tile)


def _aes_ctr_kernel(ctr_ref, pay_ref, rk_ref, sbox_ref, mul2_ref, mul3_ref,
                    shift_ref, out_ref):
    """ctr/pay/out: (BT, 16) uint8; rk: (11, 16); tables: (256,) uint8;
    shift: (16,) int32 ShiftRows permutation."""
    sbox = sbox_ref[...]
    mul2 = mul2_ref[...]
    mul3 = mul3_ref[...]
    rk = rk_ref[...]
    shift = shift_ref[...]

    def sub(state):
        return jnp.take(sbox, state.astype(jnp.int32))

    def shift_rows(state):
        return jnp.take(state, shift, axis=1)

    def mix_columns(state):
        s = state.reshape(-1, 4, 4)
        a0, a1, a2, a3 = s[:, :, 0], s[:, :, 1], s[:, :, 2], s[:, :, 3]
        i0, i1, i2, i3 = (a0.astype(jnp.int32), a1.astype(jnp.int32),
                          a2.astype(jnp.int32), a3.astype(jnp.int32))
        b0 = jnp.take(mul2, i0) ^ jnp.take(mul3, i1) ^ a2 ^ a3
        b1 = a0 ^ jnp.take(mul2, i1) ^ jnp.take(mul3, i2) ^ a3
        b2 = a0 ^ a1 ^ jnp.take(mul2, i2) ^ jnp.take(mul3, i3)
        b3 = jnp.take(mul3, i0) ^ a1 ^ a2 ^ jnp.take(mul2, i3)
        return jnp.stack([b0, b1, b2, b3], axis=-1).reshape(-1, 16)

    state = ctr_ref[...] ^ rk[0]
    for rnd in range(1, 10):
        state = mix_columns(shift_rows(sub(state))) ^ rk[rnd]
    keystream = shift_rows(sub(state)) ^ rk[10]
    out_ref[...] = pay_ref[...] ^ keystream


@functools.partial(jax.jit, static_argnames=("interpret",))
def aes_ctr_pallas(payload_u8, round_keys, ctr_blocks, *, interpret=None):
    """payload_u8: (n,) uint8; round_keys: (11,16) uint8;
    ctr_blocks: (ceil(n/16), 16) uint8 CTR input blocks. Returns (n,) uint8."""
    interpret = resolve_interpret(interpret)
    n = payload_u8.shape[0]
    n_blocks = ctr_blocks.shape[0]
    pad = n_blocks * 16 - n
    pay = jnp.pad(payload_u8, (0, pad)).reshape(n_blocks, 16)
    bpad = (-n_blocks) % BLOCK_TILE
    if bpad:
        pay = jnp.pad(pay, ((0, bpad), (0, 0)))
        ctr_blocks = jnp.pad(ctr_blocks, ((0, bpad), (0, 0)))
    nb = n_blocks + bpad
    grid = (nb // BLOCK_TILE,)
    out = pl.pallas_call(
        _aes_ctr_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCK_TILE, 16), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_TILE, 16), lambda i: (i, 0)),
            pl.BlockSpec((11, 16), lambda i: (0, 0)),
            pl.BlockSpec((256,), lambda i: (0,)),
            pl.BlockSpec((256,), lambda i: (0,)),
            pl.BlockSpec((256,), lambda i: (0,)),
            pl.BlockSpec((16,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((BLOCK_TILE, 16), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, 16), jnp.uint8),
        interpret=interpret,
    )(ctr_blocks, pay, round_keys,
      jnp.asarray(crypto._SBOX), jnp.asarray(crypto._MUL2), jnp.asarray(crypto._MUL3),
      jnp.asarray(crypto._SHIFT_ROWS, dtype=jnp.int32))
    return out.reshape(-1)[:n]
