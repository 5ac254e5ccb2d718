"""Compile accounting (copied from chip_smoke.py, PR 21): a listener of
the benchmark's own on jax's monitoring events."""


class CompileCounter:
    """Counts jax compile requests (the backend_compile_duration event
    fires for each, persistent-cache hit or not) and persistent-cache
    hits; requests - hits = programs the backend really compiled."""

    def __init__(self):
        self.requests = 0
        self.hits = 0
        self.seconds = 0.0

    def install(self):
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)
        return self

    def _duration(self, event, duration, **kw):
        if str(event).endswith("backend_compile_duration"):
            self.requests += 1
            self.seconds += float(duration)

    def _event(self, event, **kw):
        if str(event).endswith("/compilation_cache/cache_hits"):
            self.hits += 1

    def snapshot(self):
        return (self.requests, self.hits, self.seconds)

    def since(self, snap):
        req = self.requests - snap[0]
        hits = self.hits - snap[1]
        return {"requests": req, "cache_hits": hits,
                "compiled": req - hits,
                "seconds": round(self.seconds - snap[2], 3)}
