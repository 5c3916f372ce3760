"""XLA compile-activity counter via ``jax.monitoring``.

jax emits a duration event once per *backend compile* — an actual XLA
build, never a tracing-cache or compilation-cache hit — which makes it
the ground truth for "did anything recompile?".  This module keeps a
process-global count of those events, feeds the ``xla_builds_total``
counter of :mod:`repro.obs` when the registry is enabled, and backs
:func:`repro.analysis.guards.no_recompile`.

``jax.monitoring`` has no unregister API, so the listener is installed
once (idempotently) and never removed; it is a couple of integer adds
per compile, which is noise next to the compile itself.
"""

from __future__ import annotations

import threading

_LOCK = threading.Lock()
_SUBSCRIBED = False
_BUILDS = 0

from jax._src.dispatch import BACKEND_COMPILE_EVENT


def _on_event_duration(event: str, duration: float, **kwargs) -> None:
    global _BUILDS
    if event != BACKEND_COMPILE_EVENT:
        return
    with _LOCK:
        _BUILDS += 1
    # feed the metrics registry only when it is enabled; counter() on the
    # disabled registry returns the null singleton, so this stays free.
    from repro.obs.registry import registry

    registry().counter("xla_builds_total").inc()


def ensure_subscribed() -> None:
    """Install the monitoring listener (idempotent, never removed)."""
    global _SUBSCRIBED
    with _LOCK:
        if _SUBSCRIBED:
            return
        _SUBSCRIBED = True
    import jax.monitoring

    jax.monitoring.register_event_duration_secs_listener(_on_event_duration)


def builds_total() -> int:
    """XLA builds observed process-wide since :func:`ensure_subscribed`."""
    with _LOCK:
        return _BUILDS
