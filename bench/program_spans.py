"""The program's own phase spans (``serve.*``) in the traced window, and
how much of each the device was busy.

``FleetServer`` opens one ``repro.obs`` span per phase of an ingest chunk
or a tick; each is a ``jax.profiler.TraceAnnotation`` on the host plane of
the trace the run records, on the device's clock.  A child phase's name
starts with its parent's (``serve.ingest`` > ``serve.ingest.campaign``).

For the window of ``trace_reduce.reduce`` (the first ``bench.*`` span's
start to the last one's end) and its device busy set (the union of the
``XLA Ops`` intervals of each device, averaged over the devices), each span
name gets, summed over its instances and clipped to the window:

* ``seconds`` — wall time; ``count`` — instances;
* ``busy_s`` — device-busy time inside the span; ``idle_s`` — the rest;
* ``self_s`` — wall time less that of its direct children (the spans
  nested in it on its thread).

The metric readers take the run's trace, the newest ``.xplane.pb`` under
``bench/out/trace/`` (a traced run removes its cell's directory first),
parsed once per process.  A program with no ``serve.*`` spans reads
nothing: ``share`` returns None.
"""
from __future__ import annotations

from pathlib import Path

import numpy as np

import trace_reduce
from harness import OUT

SPAN_PREFIX = "serve."
_PARSED: dict = {}


def newest_trace(log_dir: Path = OUT / "trace") -> Path | None:
    found = list(Path(log_dir).rglob("*.xplane.pb"))
    return max(found, key=lambda p: p.stat().st_mtime_ns) if found else None


def _covered(merged: np.ndarray, cum: np.ndarray, t: np.ndarray):
    """Length of the disjoint sorted intervals ``merged`` before each t."""
    i = np.searchsorted(merged[:, 0], t, side="right") - 1
    j = np.maximum(i, 0)
    inside = np.clip(t - merged[j, 0], 0.0, merged[j, 1] - merged[j, 0])
    return np.where(i >= 0, cum[j] + inside, 0.0)


def _self_seconds(instances: list) -> np.ndarray:
    """Wall time of each (start, end) less its direct children's, where
    the instances are one thread's spans sorted by (start, -end)."""
    own = np.asarray([e - s for s, e in instances], np.float64)
    stack: list[int] = []
    for k, (s, e) in enumerate(instances):
        while stack and instances[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= e - s
        stack.append(k)
    return own


def phases(profile, *, plane_prefix: str = "/device:TPU:",
           op_line: str = "XLA Ops") -> dict:
    """``{"window_s": ..., "spans": {name: figures}}`` of one profile
    (``ProfileData``, or anything with its ``planes``/``lines``/``events``
    shape); ``plane_prefix`` and ``op_line`` as in ``trace_reduce``."""
    window, lines, devices = [], [], []
    for plane in profile.planes:
        if plane.name.startswith(plane_prefix):
            st, en = [], []
            for line in plane.lines:
                if line.name == op_line or (op_line.endswith("*") and
                                            line.name.startswith(op_line[:-1])):
                    for e in line.events:
                        if e.duration_ns > 0:
                            st.append(e.start_ns)
                            en.append(e.start_ns + e.duration_ns)
            devices.append((np.asarray(st, np.float64),
                            np.asarray(en, np.float64)))
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                mine = []
                for e in line.events:
                    if e.name.startswith(trace_reduce.SPAN_PREFIX):
                        window += [e.start_ns, e.start_ns + e.duration_ns]
                    elif e.name.startswith(SPAN_PREFIX):
                        mine.append((e.name, float(e.start_ns),
                                     float(e.start_ns + e.duration_ns)))
                lines.append(mine)
    if not window:
        raise ValueError(f"no '{trace_reduce.SPAN_PREFIX}*' spans in the "
                         f"trace")
    lo, hi = min(window), max(window)
    names, st, en = [], [], []
    own = []
    for mine in lines:
        mine = [(n, max(s, lo), min(e, hi)) for n, s, e in mine
                if e > lo and s < hi]
        mine.sort(key=lambda x: (x[1], -x[2]))
        names += [n for n, _, _ in mine]
        st += [s for _, s, _ in mine]
        en += [e for _, _, e in mine]
        own.append(_self_seconds([(s, e) for _, s, e in mine]))
    st, en = np.asarray(st, np.float64), np.asarray(en, np.float64)
    own = np.concatenate(own) if own else np.zeros(0)
    busy = np.zeros(len(st))
    for dst, den in devices:
        keep = (den > lo) & (dst < hi)
        _, merged = trace_reduce.union_seconds(np.clip(dst[keep], lo, hi),
                                               np.clip(den[keep], lo, hi))
        if len(merged):
            cum = np.r_[0.0, np.cumsum(merged[:, 1] - merged[:, 0])[:-1]]
            busy += _covered(merged, cum, en) - _covered(merged, cum, st)
    if devices:
        busy /= len(devices)
    spans: dict = {}
    for k, name in enumerate(names):
        f = spans.setdefault(name, {"count": 0, "seconds": 0.0,
                                    "busy_s": 0.0, "idle_s": 0.0,
                                    "self_s": 0.0})
        wall = (en[k] - st[k]) * 1e-9
        f["count"] += 1
        f["seconds"] += wall
        f["busy_s"] += busy[k] * 1e-9
        f["idle_s"] += wall - busy[k] * 1e-9
        f["self_s"] += own[k] * 1e-9
    return {"window_s": (hi - lo) * 1e-9, "spans": spans}


def current(log_dir: Path = OUT / "trace") -> dict | None:
    """``phases`` of the run's trace, parsed once per process; None when
    there is no trace."""
    path = newest_trace(log_dir)
    if path is None:
        return None
    if path not in _PARSED:
        _PARSED.clear()
        _PARSED[path] = phases(trace_reduce.load(path))
    return _PARSED[path]


def share(figure: str, names: tuple, root: str, ph: dict | None = None):
    """Sum of ``figure`` over the spans ``names``, over the window; None
    when the trace holds no ``root`` span (a program without these
    spans), 0 for a phase that did not run."""
    ph = current() if ph is None else ph
    if not ph or root not in ph["spans"] or ph["window_s"] <= 0:
        return None
    return sum(ph["spans"].get(n, {}).get(figure, 0.0)
               for n in names) / ph["window_s"]
