"""Profiling hooks: jax.profiler wrapping and compiled-program stats.

Both run only when the caller asked for them, and then they either do
what was asked or raise: a profile or a compile that silently went
missing would read as a device that did nothing.
"""

from __future__ import annotations

import re
from contextlib import contextmanager
from typing import Dict, Optional

from repro.launch.hlo_stats import collective_bytes, cost_summary, memory_summary

_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$")
_INSTRUCTION = re.compile(r"^\s+(ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_OPERANDS = re.compile(r"%([\w.\-]+)")
_IDENT = re.compile(r"[A-Za-z_][\w\-]*")


@contextmanager
def maybe_jax_profiler(trace_dir: Optional[str]):
    """``jax.profiler.trace`` around the wrapped block when ``trace_dir``
    is set, with the Python tracer off (the host events are the
    program's own spans and JAX's dispatch); a plain no-op otherwise."""
    if not trace_dir:
        yield
        return
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(trace_dir, profiler_options=opts):
        yield


def _scope_phase(op_name: str, names) -> Optional[str]:
    """The innermost path component of ``op_name`` that names a phase
    (``fit``, or ``fit`` wrapped by a transform such as ``jvp(fit)``)."""
    for part in reversed(op_name.split("/")):
        for ident in _IDENT.findall(part):
            if ident in names:
                return ident
    return None


def hlo_phases(hlo_text: str) -> Dict[str, str]:
    """``{instruction name: protocol phase}`` of a compiled program's HLO
    text, read from the ``op_name`` metadata that
    ``jax.named_scope(Phase.X.value)`` leaves on every op traced inside
    it.  A fusion takes the scope of its fused computation's root (of
    the root's operands where the root is an unnamed tuple); an
    instruction in no phase's scope maps to ``"other"``.  The names are
    the ones the profiler's ``XLA Ops`` events carry, so device time in
    a trace sums per phase."""
    from repro.core.protocol import Phase
    names = {p.value for p in Phase}
    instrs: Dict[str, tuple] = {}        # name -> (op_name, calls, operands)
    roots: Dict[str, str] = {}           # computation -> root instruction
    comp = None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if m is None:
            continue
        name = m.group(2)
        rhs = line[m.end():]
        op = _OP_NAME.search(rhs)
        calls = _CALLS.search(rhs)
        instrs[name] = (op.group(1) if op else None,
                        calls.group(1) if calls else None,
                        _OPERANDS.findall(rhs.split("), ")[0]))
        if m.group(1):
            roots[comp] = name

    def phase(name: str, depth: int = 0) -> Optional[str]:
        op_name, calls, _ = instrs.get(name, (None, None, ()))
        if calls in roots and depth < 2:
            root = roots[calls]
            root_op, _, root_operands = instrs[root]
            for inner in [root] + (root_operands if root_op is None else []):
                found = phase(inner, depth + 1)
                if found is not None:
                    return found
        return _scope_phase(op_name, names) if op_name else None

    return {name: phase(name) or "other" for name in instrs}


def jit_hlo_stats(jit_fn, *args, **kwargs) -> dict:
    """Flops/bytes/memory of ``jit_fn`` compiled for ``args``.

    Uses the AOT path (``lower(...).compile()``): lowering only reads
    abstract shapes, so calling this BEFORE the real program invocation
    is safe even when the real call donates its buffers.  The extra
    compile is why ``TraceConfig.hlo_stats`` is opt-in.  A failed
    compile raises.  ``phases`` maps each instruction to its protocol
    phase (:func:`hlo_phases`).  ``tpu_custom_calls`` counts the compiled Pallas
    kernels in the program: 0 on a backend where they run interpreted.
    """
    compiled = jit_fn.lower(*args, **kwargs).compile()
    stats: dict = {}
    stats.update(cost_summary(compiled))
    memory = memory_summary(compiled)
    if memory:
        stats["memory"] = memory
    hlo_text = compiled.as_text()
    stats["phases"] = hlo_phases(hlo_text)
    stats["tpu_custom_calls"] = hlo_text.count(
        'custom_call_target="tpu_custom_call"')
    coll = collective_bytes(hlo_text)
    if coll.get("total_collective_bytes"):
        stats["collectives"] = coll
    return stats
