"""repro.telemetry — structured observability for both EnFed engines.

The paper's contribution is an accounting argument (per-round training
time, energy, and response time — §IV-G, Tables IV/V), so the repo's
runtime evidence must be more than an ad-hoc dict-of-lists assembled
differently per engine.  This package is the one observability surface:

* **Round events** (:mod:`repro.telemetry.events`) — one
  :class:`RoundEvent` schema (round, requester, phase, membership,
  drop/retry/stale counters, delivered set, battery, accuracy, wire
  bytes, energy) materialized from EITHER engine's per-session history
  by a single adapter (:func:`session_events`).  The loop oracle and
  the compiled fleet program emit the SAME normalized stream on the
  same world — padding and buffer-layout differences are erased at
  this boundary (masks become index sets), so cross-engine equality is
  checkable event for event (:func:`compare_event_streams`).

* **Timing spans** (:mod:`repro.telemetry.spans`) — a host-side
  :class:`Timeline` of nested :class:`Span` records instrumenting the
  real cost centers: the world copy ("copy_world"), shard staging
  ("stage" and its children), jit trace/compile + warm execution
  ("program" / "chunk"), unpacking ("unpack" and its children), the
  per-session views ("views") and the result ("assemble"),
  quantize/dequantize packing ("quantize_pack" / "dequant_unpack"),
  checkpoint I/O ("checkpoint_save" / "checkpoint_restore"), and the
  loop engine's AES-CTR transport ("transport").  Every span is also a
  ``jax.profiler.TraceAnnotation``, so a profiler trace holds the spans
  on the device's clock.  ``FleetResult.timeline`` /
  ``RunResult.timeline`` carry it; ``Timeline.totals()`` is the
  wall-clock breakdown the bench publishes.

* **Exporters** (:mod:`repro.telemetry.export`) — the event stream as
  JSONL (one event per line, schema-validated round trip) and the
  Timeline as a Chrome-trace/Perfetto ``trace.json``.

* **Profiling hooks** (:mod:`repro.telemetry.profile`) — an opt-in
  ``jax.profiler`` trace around the whole ``Experiment.run`` and an
  ``hlo_stats`` summary (flops / bytes-accessed / memory of the
  compiled program, via :mod:`repro.launch.hlo_stats`, and each
  instruction's protocol phase from the fleet program's
  ``jax.named_scope`` per ``Phase``).

* **The knob** (:class:`TraceConfig` on ``ExecutionSpec.trace``) —
  selects exports and profiling hooks per run.

House rule, enforced by ``tests/test_telemetry.py`` and the bench's
trace smoke gate: **observation can never change the simulated
outcome**.  Every instrument here is host-side — wall clocks, post-hoc
history adaptation, file exports — and a run with tracing on is bitwise
identical (params, masks, battery) to the same run with tracing off.
New protocol phases or methods must keep that contract: emit events by
extending the history→event adapter, never by touching traced state.
"""

from repro.telemetry.config import TraceConfig
from repro.telemetry.events import (EVENT_PHASES, ROUND_EVENT_FIELDS,
                                    RoundEvent, compare_event_streams,
                                    session_events, validate_events)
from repro.telemetry.export import (read_events_jsonl, timeline_chrome_trace,
                                    write_chrome_trace, write_events_jsonl)
from repro.telemetry.profile import (hlo_phases, jit_hlo_stats,
                                     maybe_jax_profiler)
from repro.telemetry.spans import Span, Timeline

__all__ = [
    "TraceConfig",
    "RoundEvent",
    "ROUND_EVENT_FIELDS",
    "EVENT_PHASES",
    "session_events",
    "validate_events",
    "compare_event_streams",
    "Span",
    "Timeline",
    "write_events_jsonl",
    "read_events_jsonl",
    "timeline_chrome_trace",
    "write_chrome_trace",
    "jit_hlo_stats",
    "hlo_phases",
    "maybe_jax_profiler",
]
