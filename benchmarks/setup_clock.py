"""What set-up was spent on, from JAX's own events (copied from
chip_smoke.py's `_on_jax_duration`): seconds in the backend compiler (or, on a
persistent-cache hit, fetching the executable), seconds tracing and lowering
(these nest, so they are an upper bound), and the cache's hits and misses.
Printed on the info line; `setup_s` itself is plain wall clock."""

from __future__ import annotations

_DURATIONS = {
    "/jax/core/compile/backend_compile_duration": "backend_compile_s",
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
}
_EVENTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}


class SetupClock:
    def __init__(self):
        import jax.monitoring

        self.totals = {name: 0.0 for name in _DURATIONS.values()}
        self.totals.update({name: 0 for name in _EVENTS.values()})
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **_) -> None:
        if event in _DURATIONS:
            self.totals[_DURATIONS[event]] += seconds

    def _event(self, event: str, **_) -> None:
        if event in _EVENTS:
            self.totals[_EVENTS[event]] += 1

    def snapshot(self) -> dict:
        return dict(self.totals)
