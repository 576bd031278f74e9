"""Cross-layer tracing + decision-audit subsystem (PR 10).

Spans (how long), audit events (why), and exporters (JSONL / Perfetto)
for the whole stack — solver ladder phases and device waits, stacked
kernel launches, engine tick stages, balancer refreshes, sim steps —
under a hard zero-perturbation contract: no RNG draws, no jit-cache-key
effects, no trace state in any checkpoint. ``REPRO_TRACE=1`` turns
recording on; off is a no-op fast path. Recorded spans are also profiler
annotations (``repro.obs.trace``). See docs/OBSERVABILITY.md.
"""
from . import events, names  # noqa: F401
from .trace import (TRACER, Tracer, capture, clear, current_tick,  # noqa: F401
                    dropped, enabled, event, mark, records, set_enabled,
                    set_tick, span, timed_span, traced)

__all__ = [
    "names", "events", "Tracer", "TRACER", "enabled", "set_enabled",
    "span", "timed_span", "event", "traced", "set_tick", "current_tick",
    "mark", "records", "dropped", "clear", "capture",
]
