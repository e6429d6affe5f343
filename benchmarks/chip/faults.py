"""Faults planted in the timed path, underneath the harness.

Each breaks the program where a later change to it could: the harness
and the reference stay as they are, and the comparison that decides
``correct`` has to catch the fault.  ``calibrate.py --faults`` reads them
on the chip at a cell's own size; ``tests/test_harness.py`` plants them
at a test's size on the CPU.

* ``state_unchanged``: every Adam step returns the params it was given;
* ``half_batch``: the loss is the mean over the first half of each
  minibatch;
* ``answer_altered``: requester 0's aggregate is off by 1% where the
  kernel makes it;
* ``contributor_left_out``: the aggregate leaves the last signed
  contributor out;
* ``eval_half``: the evaluation scores only the first half of the test
  split.
"""

from __future__ import annotations

import contextlib

FAULTS = ("state_unchanged", "half_batch", "answer_altered",
          "contributor_left_out", "eval_half")


def _replacement(fault: str):
    """(attribute of ``repro.core.fleet``, its broken stand-in)."""
    import jax.numpy as jnp
    from repro.core import fleet

    if fault == "state_unchanged":
        return "apply_updates", lambda p, u: p
    if fault == "half_batch":
        loss = fleet.masked_cross_entropy_loss
        return "masked_cross_entropy_loss", lambda lg, y, w: loss(
            lg, y, w * (jnp.arange(w.shape[0]) < w.shape[0] // 2))
    if fault == "answer_altered":
        agg = fleet.fedavg_flat_batched
        return "fedavg_flat_batched", lambda u, w, **kw: agg(
            u, w, **kw).at[0].multiply(1.01)
    if fault == "contributor_left_out":
        agg = fleet.fedavg_flat_batched
        return "fedavg_flat_batched", lambda u, w, **kw: agg(
            u, w.at[:, -1].set(0.0), **kw)
    if fault == "eval_half":
        # the test split's 0/1 mask, staged by ``_pad_stack``; the other
        # arrays it stacks discard their mask
        pad = fleet._pad_stack

        def half(arrays, pad_len):
            out, mask = pad(arrays, pad_len)
            mask[:, mask.shape[1] // 2:] = 0.0
            return out, mask
        return "_pad_stack", half
    raise ValueError(f"no fault {fault!r}; known: {FAULTS}")


@contextlib.contextmanager
def planted(fault: str):
    """The program with ``fault`` planted, for the body of the block.
    JAX's in-memory caches are cleared on the way in and out, so that
    the programs are traced anew with and without the fault."""
    import jax
    from repro.core import fleet
    name, broken = _replacement(fault)
    original = getattr(fleet, name)
    jax.clear_caches()
    setattr(fleet, name, broken)
    try:
        yield
    finally:
        setattr(fleet, name, original)
        jax.clear_caches()
