"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

On a TPU the trace holds one plane per chip, ``/device:TPU:<i>``.  Its
``XLA Modules`` line has one event per program execution, named
``<jit name>(<program fingerprint>)``; its ``XLA Ops`` line has one event
per executed HLO instruction (nested: a ``while`` holds its body's ops),
named by the instruction's text, ``%<name> = <shape> <opcode>(...)``.  A
Pallas kernel is a ``custom-call`` to ``tpu_custom_call`` whose
instruction name carries the kernel's.  The host plane ``/host:CPU`` has
one line per thread; the benchmark's ``jax.profiler.TraceAnnotation``
spans (``bench.*``) and the runtime's own host events lie there, on the
same clock.

What the reduction gives, for the window from the first ``bench.*`` span's
start to the last one's end:

* ``busy_s`` — the union of the device's op intervals, averaged over the
  devices; ``window_s`` — the window's length;
* ``programs`` — per program execution: its jit name, fingerprint, start,
  duration, and the kernels (custom calls) that ran inside it;
* ``kernels`` — summed device seconds per kernel name;
* ``gaps`` — the ``TOP_GAPS`` longest idle intervals of the device in the
  window, each labelled by the ``bench.*`` span around it and the longest
  host event inside it.

``plane_prefix`` and ``op_line`` select what counts as a device; the
defaults are the TPU's.  A CPU trace has no device plane: tests point the
same reduction at the host's XLA threads.
"""
from __future__ import annotations

import re
from pathlib import Path

import numpy as np

MODULE_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
TOP_GAPS = 10
_INSTR = re.compile(r"^%([A-Za-z0-9_.\-]+) = ")
_MODULE = re.compile(r"^(.*)\((\d+)\)$")


def kernel_name(op_event_name: str) -> str | None:
    """The Pallas kernel of a ``tpu_custom_call`` op event, from its
    instruction name (``%vmap_jit_fail_prob__.9 = ... custom-call(...),
    custom_call_target="tpu_custom_call"`` -> ``fail_prob``); None for any
    other op, the runtime's own custom calls (``AllocateBuffer``) too."""
    if 'custom_call_target="tpu_custom_call"' not in op_event_name:
        return None
    m = _INSTR.match(op_event_name)
    if not m:
        return None
    name = re.sub(r"\.\d+$", "", m.group(1))
    name = re.sub(r"^(vmap_)*(jit_)?", "", name).strip("_")
    return name or None


def union_seconds(starts: np.ndarray, ends: np.ndarray) -> tuple:
    """(length of the union of [start, end) intervals, merged intervals)."""
    if len(starts) == 0:
        return 0.0, np.zeros((0, 2))
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    run_end = np.maximum.accumulate(e)
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > run_end[:-1]
    idx = np.flatnonzero(new)
    merged = np.stack([s[idx], np.r_[run_end[idx[1:] - 1], run_end[-1]]], 1)
    return float((merged[:, 1] - merged[:, 0]).sum()), merged


def load(path: Path):
    import jax
    return jax.profiler.ProfileData.from_file(str(path))


def find_trace(log_dir: Path) -> Path:
    found = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def reduce(profile, *, plane_prefix: str = "/device:TPU:",
           op_line: str = "XLA Ops") -> dict:
    spans, host = [], []
    devices = []
    for plane in profile.planes:
        if plane.name.startswith(plane_prefix):
            dev = {"start": [], "dur": [], "k_start": [], "k_dur": [],
                   "k_name": [], "mods": []}
            for line in plane.lines:
                if line.name == op_line or (op_line.endswith("*") and
                                            line.name.startswith(op_line[:-1])):
                    for e in line.events:     # names are long: keep none
                        d = e.duration_ns
                        if d <= 0:
                            continue
                        dev["start"].append(e.start_ns)
                        dev["dur"].append(d)
                        k = kernel_name(e.name)
                        if k:
                            dev["k_start"].append(e.start_ns)
                            dev["k_dur"].append(d)
                            dev["k_name"].append(k)
                elif line.name == MODULE_LINE:
                    dev["mods"] += [(e.name, e.start_ns, e.duration_ns)
                                    for e in line.events]
            devices.append(dev)
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
                    elif e.duration_ns > 0:
                        host.append((e.name, e.start_ns,
                                     e.start_ns + e.duration_ns))
    if not spans:
        raise ValueError(f"no '{SPAN_PREFIX}*' spans in the trace")
    lo = min(s for _, s, _ in spans)
    hi = max(e for _, _, e in spans)
    window_s = (hi - lo) * 1e-9
    busy, kernels, programs, gaps = [], {}, [], []
    for dev in devices:
        st = np.asarray(dev["start"], np.float64)
        en = st + np.asarray(dev["dur"], np.float64)
        keep = (en > lo) & (st < hi)
        b, merged = union_seconds(np.clip(st[keep], lo, hi),
                                  np.clip(en[keep], lo, hi))
        busy.append(b * 1e-9)
        kst = np.asarray(dev["k_start"], np.float64)
        ken = kst + np.asarray(dev["k_dur"], np.float64)
        for k, s, e in zip(dev["k_name"], kst, ken):
            if e > lo and s < hi:
                kernels[k] = kernels.get(k, 0.0) + (min(e, hi)
                                                    - max(s, lo)) * 1e-9
        for name, s, d in dev["mods"]:
            if s + d <= lo or s >= hi:
                continue
            m = _MODULE.match(name)
            inside = sorted({dev["k_name"][i] for i in np.flatnonzero(
                (kst >= s) & (kst < s + d))})
            programs.append({"name": m.group(1) if m else name,
                             "fingerprint": m.group(2) if m else "",
                             "start_s": (s - lo) * 1e-9,
                             "seconds": (min(s + d, hi) - max(s, lo)) * 1e-9,
                             "kernels": inside})
        edges = np.r_[lo, merged.ravel(), hi].reshape(-1, 2)
        for g0, g1 in edges:
            if g1 > g0:
                gaps.append((g0, g1))
    gaps.sort(key=lambda g: g[0] - g[1])
    labelled = []
    for g0, g1 in gaps[:TOP_GAPS]:
        mid = 0.5 * (g0 + g1)
        span = next((n for n, s, e in spans if s <= mid < e), "between spans")
        inside = [(min(e, g1) - max(s, g0), n) for n, s, e in host
                  if s < g1 and e > g0]
        what = max(inside)[1] if inside else "host"
        labelled.append({"label": f"{span}/{what}",
                         "seconds": (g1 - g0) * 1e-9})
    return {"window_s": window_s,
            "busy_s": float(np.mean(busy)) if busy else 0.0,
            "n_devices": len(devices), "programs": programs,
            "kernels": kernels, "gaps": labelled,
            "spans": len(spans)}


def breakdown(red: dict, top: int = 10) -> dict:
    """The ``breakdown`` of a traced result line: the programs that took
    most device time (named with the kernels inside them) and the longest
    idle gaps by what the host was doing."""
    per = {}
    for p in red["programs"]:
        key = f"{p['name']}({p['fingerprint']})" + "".join(
            f"+{k}" for k in p["kernels"])
        per[key] = per.get(key, 0.0) + p["seconds"]
    ops = sorted(per.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[g["label"], g["seconds"]]
                          for g in red["gaps"][:top]]}
