"""AES-128-CTR for model-update transport (paper §III: updates are
AES-128 encrypted during transmission; keys are exchanged at handshake).

The S-box is derived at import time from GF(2^8) arithmetic (inverse +
affine map) instead of a hard-coded table, and the implementation is
validated against the FIPS-197 test vector in the test suite.  Key
expansion runs host-side in numpy (keys are protocol state, not traced
values); block encryption is vectorized JAX over blocks so an update
stream can be enciphered on-accelerator.  ``repro.kernels.aes_ctr``
holds a Pallas kernel for the same keystream-XOR hot loop that runs in
interpret mode only: its table gathers do not compile for a TPU.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# GF(2^8) tables (built at import, host-side)
# ---------------------------------------------------------------------------


def _gf_mul(a: int, b: int) -> int:
    p = 0
    for _ in range(8):
        if b & 1:
            p ^= a
        hi = a & 0x80
        a = (a << 1) & 0xFF
        if hi:
            a ^= 0x1B
        b >>= 1
    return p


def _build_sbox() -> np.ndarray:
    inv = np.zeros(256, np.uint8)
    for x in range(1, 256):
        for y in range(1, 256):
            if _gf_mul(x, y) == 1:
                inv[x] = y
                break
    sbox = np.zeros(256, np.uint8)
    for x in range(256):
        b = int(inv[x])
        s = 0
        for i in range(8):
            bit = ((b >> i) ^ (b >> ((i + 4) % 8)) ^ (b >> ((i + 5) % 8))
                   ^ (b >> ((i + 6) % 8)) ^ (b >> ((i + 7) % 8)) ^ (0x63 >> i)) & 1
            s |= bit << i
        sbox[x] = s
    return sbox


_SBOX = _build_sbox()
_MUL2 = np.array([_gf_mul(x, 2) for x in range(256)], np.uint8)
_MUL3 = np.array([_gf_mul(x, 3) for x in range(256)], np.uint8)

# ShiftRows permutation on column-major state layout (i = row + 4*col):
# output byte (row r, col c) comes from input (row r, col (c + r) mod 4)
_SHIFT_ROWS = np.array([r + 4 * ((c + r) % 4) for c in range(4) for r in range(4)])

_RCON = np.array([0x01, 0x02, 0x04, 0x08, 0x10, 0x20, 0x40, 0x80, 0x1B, 0x36], np.uint8)


def expand_key(key: np.ndarray) -> np.ndarray:
    """AES-128 key schedule: (16,) uint8 -> (11, 16) uint8 round keys."""
    key = np.asarray(key, np.uint8)
    assert key.shape == (16,)
    words = [key[i * 4:(i + 1) * 4].copy() for i in range(4)]
    for i in range(4, 44):
        temp = words[i - 1].copy()
        if i % 4 == 0:
            temp = np.roll(temp, -1)
            temp = _SBOX[temp]
            temp[0] ^= _RCON[i // 4 - 1]
        words.append(words[i - 4] ^ temp)
    return np.stack([np.concatenate(words[i * 4:(i + 1) * 4]) for i in range(11)])


# ---------------------------------------------------------------------------
# block cipher (JAX, vectorized over blocks)
# ---------------------------------------------------------------------------

_J_SBOX = jnp.asarray(_SBOX)
_J_MUL2 = jnp.asarray(_MUL2)
_J_MUL3 = jnp.asarray(_MUL3)
_J_SHIFT = jnp.asarray(_SHIFT_ROWS)


def _mix_columns(state):
    """state: (N, 16) uint8, column-major (i = row + 4*col)."""
    s = state.reshape(-1, 4, 4)  # (N, col, row)
    a0, a1, a2, a3 = s[:, :, 0], s[:, :, 1], s[:, :, 2], s[:, :, 3]
    b0 = _J_MUL2[a0] ^ _J_MUL3[a1] ^ a2 ^ a3
    b1 = a0 ^ _J_MUL2[a1] ^ _J_MUL3[a2] ^ a3
    b2 = a0 ^ a1 ^ _J_MUL2[a2] ^ _J_MUL3[a3]
    b3 = _J_MUL3[a0] ^ a1 ^ a2 ^ _J_MUL2[a3]
    return jnp.stack([b0, b1, b2, b3], axis=-1).reshape(-1, 16)


def aes128_encrypt_blocks(blocks, round_keys):
    """blocks: (N, 16) uint8; round_keys: (11, 16) uint8 -> (N, 16) uint8."""
    state = blocks ^ round_keys[0]
    for rnd in range(1, 10):
        state = _J_SBOX[state]
        state = state[:, _J_SHIFT]
        state = _mix_columns(state)
        state = state ^ round_keys[rnd]
    state = _J_SBOX[state]
    state = state[:, _J_SHIFT]
    return state ^ round_keys[10]


# ---------------------------------------------------------------------------
# CTR mode over arbitrary payloads
# ---------------------------------------------------------------------------


def _counter_blocks(nonce: np.ndarray, n_blocks: int) -> np.ndarray:
    """nonce: (8,) uint8; returns (n_blocks, 16) uint8 CTR blocks."""
    ctr = np.arange(n_blocks, dtype=np.uint64)
    ctr_bytes = ctr[:, None].view(np.uint8).reshape(n_blocks, 8)[:, ::-1]  # big-endian
    return np.concatenate([np.broadcast_to(nonce, (n_blocks, 8)), ctr_bytes], axis=1)


def keystream(key: np.ndarray, nonce: np.ndarray, n_bytes: int):
    n_blocks = (n_bytes + 15) // 16
    rks = jnp.asarray(expand_key(key))
    blocks = jnp.asarray(_counter_blocks(np.asarray(nonce, np.uint8), n_blocks))
    ks = aes128_encrypt_blocks(blocks, rks)
    return ks.reshape(-1)[:n_bytes]


def encrypt_bytes(payload_u8, key, nonce):
    """CTR encryption: payload (n,) uint8 -> ciphertext (n,) uint8."""
    ks = keystream(key, nonce, int(payload_u8.shape[0]))
    return payload_u8 ^ ks


decrypt_bytes = encrypt_bytes  # CTR is an involution given the same keystream


def float_vector_to_bytes(vec):
    """(n,) float32 -> (4n,) uint8 via bitcast (serialization for transport)."""
    u8 = jax.lax.bitcast_convert_type(vec.astype(jnp.float32), jnp.uint8)
    return u8.reshape(-1)


def bytes_to_float_vector(u8):
    return jax.lax.bitcast_convert_type(u8.reshape(-1, 4), jnp.float32).reshape(-1)


def encrypt_update(vec, key, nonce):
    """Encrypt a flattened fp32 model update (the paper's transport unit)."""
    return encrypt_bytes(float_vector_to_bytes(vec), key, nonce)


def decrypt_update(cipher_u8, key, nonce):
    return bytes_to_float_vector(decrypt_bytes(cipher_u8, key, nonce))
