"""Host-side timing spans: ``Span`` records on a per-run ``Timeline``.

The engines' real cost centers are host-visible walls — jit
trace/compile vs warm execution, shard staging, quantize/dequant
packing, checkpoint save/restore, the loop engine's AES-CTR transport —
so the instrument is a plain ``time.perf_counter`` stack, not anything
that touches traced state (the observation-never-changes-outcome rule).
Each span is also a ``jax.profiler.TraceAnnotation`` of the same name:
under a running profiler the spans are host events in the same trace as
the device's operations, on its clock; with none running an annotation
is a no-op.

Span-name vocabulary used by the engines (``Timeline.totals()`` keys;
indented names nest in the one above them; attrs in brackets):

=========================  ===============================================
``copy_world``             ``WorldSpec.fresh_requesters`` (enfed, both
                           engines)
``stage``                  fleet host staging, parent of:
  ``handshake``            contracts and round weights (or the mobility
                           candidate descriptors)
  ``shards``               shard digests and the unique-shard tables
                           [``lanes``: signed lanes, ``shards``: unique
                           staged shards]
  ``stack``                contributor param stacking, ``tree_ravel`` and
                           its host copy
  ``quantize_pack``        int8 round-state quantization
  ``arrays``               requester data, schedule and ACCOUNT constants,
                           host->device copies [``bytes``: staged bytes]
  ``refresh_dedup``        refresh-compute dedup keys, static worlds
                           [``live_rows``: unique refresh rows]
  ``init_state``           the round-0 carry
``hlo_stats``              the opt-in AOT lower+compile for the cost summary
``program``                the one jitted fleet program call (compile
                           included on a cache miss: ``cache_miss``)
``chunk``                  one ``_fleet_chunk_program`` call of the
                           host-driven checkpoint loop
``checkpoint_save``        ``repro.checkpoint`` serialization
``checkpoint_restore``     checkpoint restore (both engines)
``unpack``                 fleet device->host unpacking, parent of:
  ``fetch``                histories, rounds, stop codes, levels to host
  ``writeback``            contributor write-back: one device->host copy,
                           per-lane numpy views [``views``: per-lane trees
                           written, ``bytes``: bytes fetched]
    ``dequant_unpack``     int8->fp32 write-back dequant
  ``unravel``              the final params' pytree
``views``                  fleet per-session ``SessionResult`` and
                           ``cost.session`` loop [``sessions``]
``assemble``               ``RunResult.from_sessions`` (enfed)
``handshake``              loop-engine contract signing + key exchange
``transport``              loop-engine AES-CTR collect of one round's updates
``fit``                    loop-engine requester fit of one round
``refresh``                loop-engine contributor refresh of one round
=========================  ===============================================
"""

from __future__ import annotations

import dataclasses
import time
from contextlib import contextmanager
from typing import Dict, List, Optional

from jax.profiler import TraceAnnotation


@dataclasses.dataclass
class Span:
    """One timed region.  ``t0``/``dur`` are seconds relative to the
    owning Timeline's epoch; ``dur < 0`` marks a span still open."""

    name: str
    t0: float
    dur: float = -1.0
    depth: int = 0
    parent: Optional[int] = None   # index into Timeline.spans
    attrs: Dict[str, object] = dataclasses.field(default_factory=dict)


class Timeline:
    """An append-only list of (possibly nested) spans for one run.

    Recording is always on in the engines — a span costs two
    ``perf_counter`` reads, one small object and one profiler
    annotation (a no-op when no profiler runs), and records nothing
    that can feed back into the simulation.  Use :meth:`span` as a
    context manager for small regions, or :meth:`begin`/:meth:`finish`
    around regions that are awkward to indent.
    """

    def __init__(self):
        self.spans: List[Span] = []
        self._stack: List[int] = []
        self._annotations: List[TraceAnnotation] = []
        self._epoch = time.perf_counter()

    def begin(self, name: str, **attrs) -> int:
        """Open a span; returns its index for :meth:`finish`."""
        annotation = TraceAnnotation(name)
        annotation.__enter__()
        self._annotations.append(annotation)
        idx = len(self.spans)
        self.spans.append(Span(
            name=name, t0=time.perf_counter() - self._epoch,
            depth=len(self._stack),
            parent=self._stack[-1] if self._stack else None,
            attrs=dict(attrs)))
        self._stack.append(idx)
        return idx

    def finish(self, idx: int, **attrs) -> None:
        """Close the span opened by :meth:`begin` (strictly LIFO),
        adding ``attrs`` (counts known only at its end)."""
        if not self._stack or self._stack[-1] != idx:
            raise RuntimeError(
                f"span {idx} is not the innermost open span "
                f"(stack: {self._stack})")
        self._stack.pop()
        sp = self.spans[idx]
        sp.dur = time.perf_counter() - self._epoch - sp.t0
        sp.attrs.update(attrs)
        self._annotations.pop().__exit__(None, None, None)

    @contextmanager
    def span(self, name: str, **attrs):
        idx = self.begin(name, **attrs)
        try:
            yield self.spans[idx]
        finally:
            self.finish(idx)

    def totals(self) -> Dict[str, float]:
        """Summed duration (s) per span name — the wall-clock breakdown.
        Nested spans count under their own name AND inside their
        parent's duration (so e.g. ``quantize_pack`` is a sub-slice of
        ``stage``, not additive with it)."""
        out: Dict[str, float] = {}
        for sp in self.spans:
            if sp.dur >= 0:
                out[sp.name] = out.get(sp.name, 0.0) + sp.dur
        return out

    def total(self, name: str) -> float:
        return self.totals().get(name, 0.0)
