"""Backend compiles and persistent-cache loads, from JAX's monitoring
events (the chip smoke's ``CompileClock``, with a mark for the window).
"""
from __future__ import annotations


class CompileClock:
    def __init__(self):
        from jax import monitoring
        self.seconds, self.compiles, self.cache_hits = 0.0, 0, 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration
                self.compiles += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)

    def programs(self) -> int:
        """Programs compiled or loaded from the persistent cache so far."""
        return self.compiles + self.cache_hits

    def line(self) -> str:
        return (f"{self.seconds:.2f} s backend compile over {self.compiles} "
                f"executables, {self.cache_hits} persistent-cache hits")
