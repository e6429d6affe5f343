"""The program's own spans and protocol phases in a JAX profiler trace.

Every ``repro.telemetry.Timeline`` span is also a
``jax.profiler.TraceAnnotation`` of the same name, and the fleet
program wraps each protocol phase of its round body in a
``jax.named_scope``.  So a traced study holds the program's spans as
host events on the device's clock, and ``TraceConfig(hlo_stats=True)``
maps each of the compiled program's instructions to its phase
(``hlo_stats["phases"]``, from the ``op_name`` metadata).

* ``host_spans``: the study's annotation cut by the program's spans,
  each instant under the innermost span's path (``unpack/writeback``),
  so that ``devtrace.reduce`` labels an idle gap by the span the host
  spent most of it in;
* ``phase_time``: the device self time of the fleet program's
  operations, per phase;
* ``phase_map`` / ``phase_ms``: the map for a cell's program and one
  phase's device milliseconds in the traced study, as the
  ``<phase>_device_ms`` metrics read them.

A program whose ``repro.telemetry.profile`` has no ``hlo_phases`` names
no phases: there ``phase_map`` and ``phase_ms`` return ``None``.
"""

from __future__ import annotations

import types
from typing import Dict, List, Optional, Tuple

import devtrace

PROGRAM = "jit__fleet_program"
UNMAPPED = "unmapped"


def host_spans(profile, annotation: str,
               names) -> List[Tuple[str, float, float]]:
    """The annotation's window cut by the program's spans: (path,
    start_ns, end_ns) segments in time order, each instant under the
    innermost span that covers it, for ``devtrace.reduce`` to label an
    idle gap by the span the host spent most of it in.  Spans are the
    host events named in ``names`` on the annotation's own thread; a
    path joins the names of the spans that enclose the instant
    (``unpack/writeback``).  Instants in no span are not covered."""
    w0, w1 = devtrace.annotation_window(profile, annotation)
    [line] = [line for plane in profile.planes
              if plane.name.startswith("/host:") for line in plane.lines
              if any(e.name == annotation for e in line.events)]
    events = sorted(((e.name, e.start_ns, e.start_ns + e.duration_ns)
                     for e in line.events if e.name in names
                     and e.start_ns >= w0
                     and e.start_ns + e.duration_ns <= w1),
                    key=lambda e: (e[1], -e[2]))
    out, stack = [], []              # stack of [path, end_ns, cursor_ns]
    for name, a, b in events + [("", w1, w1)]:
        while stack and stack[-1][1] <= a:
            path, end, cursor = stack.pop()
            if end > cursor:
                out.append((path, cursor, end))
            if stack:
                stack[-1][2] = end
        if not name:
            break
        if stack:
            if a > stack[-1][2]:
                out.append((stack[-1][0], stack[-1][2], a))
            path = f"{stack[-1][0]}/{name}"
        else:
            path = name
        stack.append([path, b, a])
    return out


def phase_time(reduced: dict, phases: Dict[str, str]) -> Dict[str, float]:
    """Seconds of device self time per phase of the fleet program's
    operations in a reduced trace.  An instruction the map lacks counts
    under ``unmapped``: the map then belongs to another compile."""
    out: Dict[str, float] = {}
    for op in reduced["ops"].values():
        if op["module"] == PROGRAM:
            phase = phases.get(op["instr"], UNMAPPED)
            out[phase] = out.get(phase, 0.0) + op["seconds"]
    return out


def _compile_phases(rec) -> Optional[Dict[str, str]]:
    from repro.telemetry import profile
    if not hasattr(profile, "hlo_phases"):
        return None
    import bench
    from repro.api import ExecutionSpec, Experiment
    from repro.telemetry import TraceConfig
    cell = types.SimpleNamespace(model=rec["model"], conf=rec["conf"],
                                 traffic=rec["traffic"])
    spec, method, _ = bench.build(cell, 0)       # every seed, one shape
    result = Experiment(spec, method, ExecutionSpec(
        engine="fleet", trace=TraceConfig(hlo_stats=True))).run()
    return result.hlo_stats["phases"]


def phase_map(rec) -> Optional[Dict[str, str]]:
    """The cell's fleet program as ``{instruction: phase}``: one more
    study of the cell's shapes, compiled with ``TraceConfig(hlo_stats=
    True)`` (the compile cache gives the traced study's program), once
    per record."""
    if "phase_map" not in rec:
        rec["phase_map"] = _compile_phases(rec)
    return rec["phase_map"]


def phase_ms(rec, phase: str) -> Optional[float]:
    """Device milliseconds of the fleet program's ``phase`` in the
    traced study."""
    if rec["device_trace"] is None:
        return None
    phases = phase_map(rec)
    if phases is None:
        return None
    seconds = phase_time(rec["device_trace"], phases)
    if seconds.get(UNMAPPED):
        raise RuntimeError(
            f"{seconds[UNMAPPED]:.6f} s of the traced program's device "
            "time ran instructions the phase map does not name")
    return 1e3 * seconds.get(phase, 0.0)
