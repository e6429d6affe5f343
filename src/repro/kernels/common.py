"""Shared kernel-launch policy.

Every Pallas kernel in ``repro.kernels`` takes an ``interpret`` flag.
``interpret=True`` runs the kernel body as a jax interpreter program
(correct on any backend, used by the CPU test/CI tier);
``interpret=False`` compiles the kernel for the accelerator.  Callers
that don't care pass ``None`` and get the right default for the active
backend: real compilation on TPU, interpret mode everywhere Pallas
cannot lower natively (CPU CI images, laptops).
"""

from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Resolve an ``interpret`` request against the active backend.

    ``None`` (the default everywhere) means "interpret only if the
    backend cannot compile Pallas", i.e. ``jax.default_backend() ==
    "cpu"``.  Explicit ``True``/``False`` is passed through, so tests can
    force interpret mode and TPU users can force compilation.
    """
    if interpret is None:
        return jax.default_backend() == "cpu"
    return bool(interpret)


# VMEM a kernel's main input block may take per grid step.  Pallas
# double-buffers every block and the kernel body adds fp32 temporaries
# of the same size, so this stays well under the 16 MiB scoped VMEM a
# TPU v5e grants a kernel by default.
BLOCK_BYTES = 2 << 20
SUBLANES = 8


def slab_bytes(n: int, tile: int) -> int:
    """VMEM bytes of one ``(n, tile)`` fp32 slab of a 3-D block: Mosaic
    lays the second-minor axis out in 8-row sublane tiles, so ``n`` is
    padded up to a multiple of 8."""
    return -(-n // SUBLANES) * SUBLANES * tile * 4


def tile_rows(rows: int, row_bytes: int) -> int:
    """Rows of the leading (requester or batch) axis per grid step.

    The whole axis when it fits :data:`BLOCK_BYTES` (a block dim equal
    to the array dim is always legal), else the largest multiple of 8
    that fits, at least 8: the row axis is the second-minor dim of the
    ``(rows, N)`` weight and ``(rows, tile)`` output blocks, which
    Mosaic requires to be a multiple of 8 or the full extent.  Callers
    pad the axis up to a multiple of the tile.
    """
    fit = max(1, BLOCK_BYTES // max(row_bytes, 1))
    if rows <= fit:
        return rows
    return max(SUBLANES, fit // SUBLANES * SUBLANES)
