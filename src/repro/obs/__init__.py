"""repro.obs — metrics, tracing, and on-device counters.

Three layers (see README "Observability"):

* ``registry()`` — the process-global ``Registry``: counters, gauges,
  streaming histograms, nested spans.  Disabled by default (a no-op but
  for ``span()``, which always opens a profiler annotation); enable with
  ``obs.enable()`` or ``REPRO_OBS=1``.
* ``device`` — trace-time taps that turn link-mask draws inside jitted
  programs into the ``DeviceCounters`` pytree threaded through the
  slot-pool engine state (harvested host-side only at sync points).
* ``exporters`` — JSONL event log, Prometheus text, and the
  ``jax.profiler.trace`` wrapper.
"""

from repro.obs import device, exporters, stats, xla
from repro.obs.log import get_logger
from repro.obs.registry import Registry, disable, enable, registry

# The DeviceCounters pytree constructor (the engine threads it as state).
DeviceCounters = device.counter_zeros

# Count XLA builds from process start: the xla_builds_total counter and
# the analysis.guards.no_recompile() guard share this one subscription.
xla.ensure_subscribed()

__all__ = [
    "Registry",
    "registry",
    "enable",
    "disable",
    "get_logger",
    "stats",
    "device",
    "exporters",
    "xla",
    "DeviceCounters",
]
