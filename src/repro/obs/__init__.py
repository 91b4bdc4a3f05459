"""Fleet observability: metrics registry + span tracing (ARCHITECTURE 3h).

One process-global ``REGISTRY`` of counters/gauges/histograms and spans
written into the JAX profiler's trace, threaded through every hot layer —
substrate compile caches, kernel dispatch, streaming chunk scans, the
FleetServer serving paths, checkpointing, and the launch drivers.

The load-bearing rule: **instrumentation lives strictly at host
boundaries** — a counter bumps when Python runs (trace time, cache miss,
chunk boundary), a span wraps a host call — never inside jitted/scanned
code.  Consequently enabling or disabling observability is bitwise
output-invariant and adds zero compiles (asserted in tests/test_obs.py),
and disabled mode costs one branch per event.

    from repro import obs
    obs.REGISTRY.counter("repro_my_events_total").inc()
    with obs.span("layer.section") as sp:
        ...
    print(obs.REGISTRY.prometheus_text())

``obs.disable()`` / ``obs.enable()`` flip the metrics registry; spans
reach a trace while a profiler session records (``jax.profiler.trace``,
or ``obs.record(path)``, which writes the session as Chrome trace-event
JSON).
"""
from repro.obs.metrics import (DEFAULT_BUCKETS, Counter, Gauge, Histogram,
                               Metric, REGISTRY, Registry)
from repro.obs.tracing import Span, record, span


def enable() -> None:
    REGISTRY.enabled = True


def disable() -> None:
    """Freeze every metric (reads still work, events become one branch).
    Spans still reach a recording profiler session."""
    REGISTRY.enabled = False


def enabled() -> bool:
    return REGISTRY.enabled


def peak_rss_mb() -> float:
    """This process's true peak resident set in MB.

    Reads ``VmHWM`` from ``/proc/self/status`` rather than
    ``getrusage().ru_maxrss``: on Linux the rusage high-water mark is
    carried ACROSS ``execve``, so a subprocess forked from a fat parent
    (a mid-suite pytest at several GB) reports the parent's peak, not its
    own — every RSS-budget child here was silently measuring its parent.
    ``VmHWM`` lives in the fresh post-exec ``mm`` and only counts this
    process.  Falls back to ru_maxrss where /proc is unavailable."""
    try:
        with open("/proc/self/status") as fh:
            for line in fh:
                if line.startswith("VmHWM:"):
                    return int(line.split()[1]) / 1024.0
    except OSError:
        pass
    import resource
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


__all__ = [
    "DEFAULT_BUCKETS", "Counter", "Gauge", "Histogram", "Metric",
    "REGISTRY", "Registry", "Span", "disable", "enable", "enabled",
    "peak_rss_mb", "record", "span",
]
