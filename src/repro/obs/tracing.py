"""Host-side spans, written into the JAX profiler's trace.

A span is a context manager around host code.  On entry it opens a
``jax.profiler.TraceAnnotation`` of its name; on exit it waits for an
optional bound device value (``jax.block_until_ready``, so a device-bound
span covers the compute and not only the dispatch), closes the annotation
and observes its ``perf_counter`` duration into an optional ``Histogram``.
The annotation closes after the wait, so the profiler's event covers it.

While a profiler session records (``jax.profiler.trace`` / ``start_trace``)
each span is an event of the host plane, on the same clock as the device's
ops and programs, under exactly its name; its keyword args become the
event's metadata (stats), never part of its name.  With no session
recording, the annotation is one native no-op and a span is two clock
reads.

Spans live strictly at HOST boundaries — around jitted calls, never inside
them (a span inside traced code would run at trace time and measure
nothing).  Because a span only reads clocks and blocks on already-scheduled
work, recording cannot change any computed value or add any compile: the
bit-parity + no-retrace contract, asserted in tests/test_obs.py.

Naming: a child span's name starts with its parent's (``serve.ingest`` >
``serve.ingest.campaign``), so a reader of the trace can tell the phase
from the name alone and the innermost phase sorts last among equals.

    from repro import obs
    with obs.record("trace.json"):
        with obs.span("serve.ingest", n=256) as sp:
            out = server.ingest()
            sp.bind(out)                 # block on it at span close

``record`` writes the profiler's Perfetto export: Chrome trace-event JSON
(a ``traceEvents`` list in microseconds) holding the spans and the
device's events, loadable as-is in Perfetto / chrome://tracing.
"""
from __future__ import annotations

import contextlib
import gzip
import shutil
import tempfile
import time
from pathlib import Path

import jax


class Span:
    """One timed section.  ``bind(value)`` registers a jax pytree to
    ``block_until_ready`` at exit; ``set(**kv)`` attaches trace metadata;
    ``duration_s`` is readable after exit (the stats the launch/bench
    drivers report — one code path for timings and traces)."""

    __slots__ = ("name", "args", "hist", "_bound", "_t0", "_note",
                 "duration_s")

    def __init__(self, name: str, hist=None, **args):
        self.name = name
        self.args = args
        self.hist = hist
        self._bound = None
        self._t0 = 0.0
        self._note = None
        self.duration_s = 0.0

    def bind(self, value) -> "Span":
        self._bound = value
        return self

    def set(self, **kv) -> "Span":
        self.args.update(kv)
        if self._note is not None:
            self._note.set_metadata(**kv)
        return self

    def __enter__(self) -> "Span":
        self._note = jax.profiler.TraceAnnotation(self.name, **self.args)
        self._note.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if self._bound is not None:
            jax.block_until_ready(self._bound)
            self._bound = None
        self.duration_s = time.perf_counter() - self._t0
        self._note.__exit__(exc_type, exc, tb)
        self._note = None
        if self.hist is not None:
            self.hist.observe(self.duration_s)


def span(name: str, hist=None, **args) -> Span:
    """The canonical entry point: ``with span("layer.what", key=...) as sp``."""
    return Span(name, hist=hist, **args)


@contextlib.contextmanager
def record(path):
    """Record a profiler trace of the enclosed code (host spans and device
    events, no Python function tracer) and write its Chrome trace-event
    JSON to ``path``, also when the code raises."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as tmp:
        try:
            with jax.profiler.trace(tmp, create_perfetto_trace=True,
                                    profiler_options=opts):
                yield
        finally:                      # a failed run's trace is kept too
            for found in sorted(Path(tmp).rglob("perfetto_trace.json.gz")):
                with gzip.open(found, "rb") as src, open(path, "wb") as dst:
                    shutil.copyfileobj(src, dst)
