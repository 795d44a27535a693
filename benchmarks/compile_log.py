"""XLA compile requests and persistent-cache hits, counted from ``jax.monitoring``.

A copy of ``chip_smoke.CompileLog`` (PR 21): the benchmark imports nothing from the
smoke test."""


class CompileLog:
    def __init__(self):
        import jax
        self.counts = {"compiles": 0, "compile_s": 0.0, "cache_requests": 0, "cache_hits": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.counts["compiles"] += 1
            self.counts["compile_s"] += seconds

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.counts["cache_requests"] += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.counts["cache_hits"] += 1

    def mark(self):
        return dict(self.counts)

    def since(self, mark):
        return {k: self.counts[k] - mark[k] for k in mark}
