"""Reduction of a JAX profiler trace to the benchmark's device numbers.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it.  The traced window is one host
event, the ``jax.profiler.TraceAnnotation`` the benchmark puts around a
study.  On each TPU plane the ``XLA Modules`` line holds one event per
program run, and the ``XLA Ops`` line one per HLO instruction run, named
by its HLO text (``%fedavg_batched_pallas.2 = f32[1,4096]{...}
custom-call(f32[1,5]{...} %get-tuple-element.904, ...)``).  Control flow
(``while``, ``conditional``) nests: its event spans its body's events.

``reduce`` clips everything to the window and gives

* ``busy_s``: the union of the operations' intervals, averaged over the
  devices that ran any;
* ``ops``: per ``<program>/<instruction>``, its self time in seconds
  (the time no nested operation covers), how many times it ran, its
  opcode, output shape and operands;
* ``gaps``: the intervals in which no device ran an operation, each
  labelled with the host span that overlaps it most, or ``outside``.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Tuple

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

_OPCODE = re.compile(r"[\]\}\)] ([a-z][\w\-]*)\(")
_OPERAND = re.compile(r"%([\w\.\-]+)")
_LAYOUT = re.compile(r"\{[^}]*\}")


def xplane_file(log_dir: str) -> str:
    found = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {found}")
    return found[0]


def parse_op(text: str) -> dict:
    """Instruction name, opcode, output shape (no layout) and operand
    names of one ``XLA Ops`` event name."""
    lhs, _, rhs = text.partition(" = ")
    m = _OPCODE.search(rhs)
    if m is None:
        return {"instr": lhs.lstrip("%"), "opcode": "", "shape": "",
                "operands": []}
    depth, end = 1, m.end()
    while end < len(rhs) and depth:
        depth += {"(": 1, ")": -1}.get(rhs[end], 0)
        end += 1
    return {"instr": lhs.lstrip("%"), "opcode": m.group(1),
            "shape": _LAYOUT.sub("", rhs[:m.start() + 1]).strip(),
            "operands": _OPERAND.findall(rhs[m.end():end])}


def annotation_window(profile, name: str) -> Tuple[float, float]:
    """(start_ns, end_ns) of the one host event called ``name``."""
    hits = [(e.start_ns, e.start_ns + e.duration_ns)
            for plane in profile.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events if e.name == name]
    if len(hits) != 1:
        raise RuntimeError(f"expected one host event {name!r}, found "
                           f"{len(hits)}")
    return hits[0]


def _union(intervals):
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def _label(a: float, b: float, spans) -> str:
    best, best_overlap = "outside", 0.0
    for name, s0, s1 in spans:
        overlap = min(b, s1) - max(a, s0)
        if overlap > best_overlap:
            best, best_overlap = name, overlap
    return best


def _self_times(events):
    """[(name, start, end, self_ns)] of one line whose events nest."""
    out, stack = [], []             # stack of indices into out
    for name, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and out[stack[-1]][2] <= a:
            stack.pop()
        if stack:
            out[stack[-1]][3] -= b - a
        out.append([name, a, b, b - a])
        stack.append(len(out) - 1)
    return out


def _device_lines(profile):
    """Per TPU plane: its (op events, module events) inside lists of
    (name, start_ns, end_ns)."""
    out = {}
    for plane in profile.planes:
        if not plane.name.startswith(DEVICE_PLANE):
            continue
        lines = {line.name: [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events] for line in plane.lines}
        if OPS_LINE in lines:
            out[plane.name] = (lines[OPS_LINE], lines.get(MODULES_LINE, []))
    return out


def reduce(profile, annotation: str, host_spans=()) -> dict:
    """The device numbers of the window ``annotation`` marks.

    ``host_spans``: (name, start_ns, end_ns) on the trace's clock."""
    w0, w1 = annotation_window(profile, annotation)
    per_device = _device_lines(profile)
    if not per_device:
        raise RuntimeError(f"the trace holds no TPU plane with an "
                           f"{OPS_LINE!r} line")
    ops: Dict[str, dict] = {}
    busy, every = [], []
    for op_events, modules in per_device.values():
        inside = [(n, a, b) for n, a, b in op_events if a >= w0 and b <= w1]
        if not inside:
            continue
        mods = sorted((a, b, n.split("(")[0]) for n, a, b in modules)
        starts = [m[0] for m in mods]
        for name, a, b, self_ns in _self_times(inside):
            k = bisect.bisect_right(starts, a) - 1
            mod = mods[k][2] if k >= 0 and b <= mods[k][1] else ""
            op = parse_op(name)
            key = f"{mod}/{op['instr']}"
            rec = ops.setdefault(key, dict(op, module=mod, seconds=0.0,
                                           count=0))
            rec["seconds"] += self_ns * 1e-9
            rec["count"] += 1
        merged = _union([(a, b) for _, a, b in inside])
        busy.append(sum(b - a for a, b in merged) * 1e-9)
        every.extend(merged)
    gaps, cursor = [], w0
    for a, b in _union(every) + [[w1, w1]]:
        if a > cursor:
            gaps.append((_label(cursor, a, host_spans), (a - cursor) * 1e-9))
        cursor = max(cursor, b)
    return {"window_s": (w1 - w0) * 1e-9,
            "busy_s": sum(busy) / len(busy) if busy else 0.0,
            "devices": len(busy), "ops": ops, "gaps": gaps}


def kernel_time(reduced: dict, instr_prefix: str) -> Tuple[float, int]:
    """(seconds, launches) of the instructions named ``instr_prefix*``
    and of the ``pad`` instructions of the same program that feed them
    (the kernel wrapper's padding of its operands, which reads them from
    HBM); 0 launches where the trace holds none."""
    ops = reduced["ops"]
    seconds, launches = 0.0, 0
    for op in ops.values():
        if not op["instr"].startswith(instr_prefix):
            continue
        seconds += op["seconds"]
        launches += op["count"]
        for name in op["operands"]:
            feed = ops.get(f"{op['module']}/{name}")
            if feed is not None and feed["opcode"] == "pad":
                seconds += feed["seconds"]
    return seconds, launches
