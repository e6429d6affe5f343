"""TraceConfig — the ``ExecutionSpec.trace`` knob.

An execution knob in the strict repro.api sense: it selects which
observability artifacts a run emits (event JSONL, Chrome trace, jax
profiler dump, HLO cost summary) and must NEVER change the simulated
outcome — parity between traced and untraced runs is bitwise
(params/masks/battery), enforced by tests/test_telemetry.py and the
bench trace smoke gate.
"""

from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass(frozen=True)
class TraceConfig:
    """What to export/profile for one run.  All fields default off;
    a default ``TraceConfig()`` still costs nothing beyond the always-on
    host-side Timeline.

    ``events_jsonl`` / ``chrome_trace`` are written by the
    ``Experiment.run`` facade after the run completes (host-side file
    I/O, outcome-neutral).  ``jax_profiler_dir`` wraps the whole
    ``Experiment.run``, on either engine, in ``jax.profiler.trace``
    with the Python tracer off: the run's ``Timeline`` spans are host
    events of that trace, beside the device's operations, so staging,
    the program, unpacking and the facade all show on one clock.
    ``hlo_stats`` lowers and compiles the fleet program a second time
    through the AOT API to report flops/bytes
    (:mod:`repro.launch.hlo_stats`) and each instruction's protocol
    phase (``phases``) — nothing is executed, but the extra compile
    makes it strictly opt-in (fleet engine only — the loop engine warns
    and ignores it).
    """

    events_jsonl: Optional[str] = None   # write the RoundEvent stream here
    chrome_trace: Optional[str] = None   # write the Timeline as trace.json
    jax_profiler_dir: Optional[str] = None  # jax.profiler.trace around
                                            # the whole Experiment.run
    hlo_stats: bool = False              # attach compiled-program flops/bytes
