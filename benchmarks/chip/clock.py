"""Seconds JAX spends tracing, lowering and compiling, read from
``jax.monitoring``.

Copied from ``chip_smoke.py``'s ``CompileClock`` and extended with the
wall-clock interval of each event, so that the trace reducer can name
an idle gap on the device by the host work that was going on in it.
``compile_s`` includes loading an executable from the persistent
compilation cache: JAX times both under the same event.
"""

from __future__ import annotations

EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "compile_s",
}


class CompileClock:
    """Running totals of the three compile-path durations, the number
    of backend compiles and of persistent-cache hits, and the intervals
    ``(start_s, end_s)`` on ``time.time()``'s clock."""

    def __init__(self, jax):
        self._monitoring = jax.monitoring
        self.totals = {k: 0.0 for k in EVENTS.values()}
        self.counts = {"compiles": 0, "cache_hits": 0}
        self.intervals: list[tuple[float, float]] = []
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_time_span_listener(self._span)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        key = EVENTS.get(event)
        if key is not None:
            self.totals[key] += secs
            if key == "compile_s":
                self.counts["compiles"] += 1

    def _span(self, event, start, end, **_):
        if event in EVENTS:
            self.intervals.append((start, end))

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.counts["cache_hits"] += 1

    def snapshot(self) -> dict:
        """Totals and counts so far, to subtract from a later one."""
        return {**self.totals, **self.counts,
                "seconds": sum(self.totals.values())}

    def close(self):
        """Stop listening (the listeners are process-wide)."""
        m = self._monitoring
        m.unregister_event_duration_listener(self._duration)
        m.unregister_event_time_span_listener(self._span)
        m.unregister_event_listener(self._event)

    @staticmethod
    def since(before: dict, after: dict) -> dict:
        return {k: after[k] - before[k] for k in after}
