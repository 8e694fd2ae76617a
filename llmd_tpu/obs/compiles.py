"""Every XLA compile of the process, counted where it happens.

``llmd_tpu:program_compiles_total`` watches the jit caches of the registered
step programs only; a helper jitted anywhere else (a sampler variant, a
staging gather, a probe) compiles unseen, possibly inside a measured window.
JAX reports each executable it builds, or loads from the persistent cache, as
a duration event with the jitted function's name: one process-wide listener
forwards those to every live engine's counters and flight recorder.
"""

from __future__ import annotations

import threading
import weakref

import jax.monitoring

__all__ = ["watch_xla_compiles"]

# jax._src.dispatch.BACKEND_COMPILE_EVENT: around compile_or_get_cached
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_lock = threading.Lock()
_watches: "weakref.WeakSet[_Watch]" = weakref.WeakSet()
_listening = False


class _Watch:
    """One engine's sink; the engine holds it, the listener only weakly."""

    def __init__(self, metrics, flight) -> None:
        self.metrics, self.flight = metrics, flight

    def compiled(self, seconds: float, fun_name) -> None:
        self.metrics.xla_compiles.inc()
        self.metrics.xla_compile_seconds.inc(max(0.0, seconds))
        if fun_name and self.flight is not None:
            self.flight.record_system("xla_compile",
                                      seconds=round(seconds, 4),
                                      fun_name=str(fun_name))


def _on_duration(event: str, duration_secs: float, **kwargs) -> None:
    if event != BACKEND_COMPILE_EVENT:
        return
    with _lock:
        watches = list(_watches)
    for w in watches:
        try:
            w.compiled(duration_secs, kwargs.get("fun_name"))
        except Exception:  # noqa: BLE001: a listener must never fail a compile
            pass


def watch_xla_compiles(metrics, flight=None) -> _Watch:
    """Count this process's XLA compiles into ``metrics`` (``xla_compiles``,
    ``xla_compile_seconds``) and log named ones as ``xla_compile`` system
    events. Keep the returned object alive for as long as they should count;
    the JAX listener itself is registered once per process."""
    global _listening
    watch = _Watch(metrics, flight)
    metrics.xla_compiles.inc(0)  # the series exist from the first scrape
    metrics.xla_compile_seconds.inc(0)
    with _lock:
        _watches.add(watch)
        if not _listening:
            jax.monitoring.register_event_duration_secs_listener(_on_duration)
            _listening = True
    return watch
