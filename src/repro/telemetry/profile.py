"""Profiling hooks: jax.profiler wrapping and compiled-program stats.

Both run only when the caller asked for them, and then they either do
what was asked or raise: a profile or a compile that silently went
missing would read as a device that did nothing.
"""

from __future__ import annotations

from contextlib import contextmanager
from typing import Optional

from repro.launch.hlo_stats import collective_bytes, cost_summary, memory_summary


@contextmanager
def maybe_jax_profiler(trace_dir: Optional[str]):
    """``jax.profiler.trace`` around the wrapped block when ``trace_dir``
    is set; a plain no-op otherwise."""
    if not trace_dir:
        yield
        return
    import jax
    with jax.profiler.trace(trace_dir):
        yield


def jit_hlo_stats(jit_fn, *args, **kwargs) -> dict:
    """Flops/bytes/memory of ``jit_fn`` compiled for ``args``.

    Uses the AOT path (``lower(...).compile()``): lowering only reads
    abstract shapes, so calling this BEFORE the real program invocation
    is safe even when the real call donates its buffers.  The extra
    compile is why ``TraceConfig.hlo_stats`` is opt-in.  A failed
    compile raises.  ``tpu_custom_calls`` counts the compiled Pallas
    kernels in the program: 0 on a backend where they run interpreted.
    """
    compiled = jit_fn.lower(*args, **kwargs).compile()
    stats: dict = {}
    stats.update(cost_summary(compiled))
    memory = memory_summary(compiled)
    if memory:
        stats["memory"] = memory
    hlo_text = compiled.as_text()
    stats["tpu_custom_calls"] = hlo_text.count(
        'custom_call_target="tpu_custom_call"')
    coll = collective_bytes(hlo_text)
    if coll.get("total_collective_bytes"):
        stats["collectives"] = coll
    return stats
