"""Counts JAX's own trace / compile / persistent-cache events (copied from
`chip_smoke.py`): the numbers hold whether or not the program's jitcheck is
armed."""

import jax.monitoring as mon


class CompileLog:
    def __init__(self):
        self.n = {"traces": 0, "programs": 0, "cache_hits": 0,
                  "cache_misses": 0}
        self.compile_s = 0.0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/jaxpr_trace_duration":
            self.n["traces"] += 1
        elif event == "/jax/core/compile/backend_compile_duration":
            # one per program handed to the backend: compiled, or loaded
            # from the persistent cache
            self.n["programs"] += 1
            self.compile_s += secs

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.n["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.n["cache_misses"] += 1

    def snapshot(self) -> dict:
        return dict(self.n, compile_s=self.compile_s)
