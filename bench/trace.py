"""From a profiler trace to the numbers the per-layer metrics read.

``extract`` reads the ``.xplane.pb`` the JAX profiler writes and keeps two
lists, on the profiler's one clock (nanoseconds):

* device ops: every event of a TPU plane's "XLA Ops" line, named by its
  HLO instruction (``fusion.344``, ``while.9``);
* host spans: the harness's own annotations (``train_step``,
  ``input_build``, ``window_start``, ``window_end``).

``reduce`` takes that record and returns, over the traced window: the
union of device busy intervals, the ops that took the most time, and the
idle gaps labelled by the host span they fall in.  Control-flow ops
(``while``, ``conditional``, ``call``) span the ops of their bodies, which
the trace also holds, so they are left out.  The trace does not name the
JAX scope of an op, so it cannot split forward and backward from the
update phase.  Records of chip runs, cut to two train steps with the window
markers moved to their edges, are kept under ``bench/traces/`` for the test.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
from collections import defaultdict

HOST_SPANS = ("train_step", "input_build", "window_start", "window_end")
CONTAINERS = ("while", "conditional", "call")
GAP_LABELS = {"input_build": "input build", "train_step": "step call"}


def op_name(text: str) -> str:
    """``%fusion.344 = bf16[...] fusion(...)`` -> ``fusion.344``."""
    return text.split(" = ", 1)[0].lstrip("%").strip()


def extract(log_dir: str) -> dict:
    """Device ops and host spans of the one trace under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one xplane.pb under {log_dir}: {paths}")
    data = ProfileData.from_file(paths[0])
    ops, spans = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops += [[op_name(e.name), int(e.start_ns),
                             int(e.duration_ns), plane.name]
                            for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [[e.name, int(e.start_ns), int(e.duration_ns)]
                          for e in line.events if e.name in HOST_SPANS]
    return {"device_ops": ops, "host_spans": spans}


def load(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def window_of(record: dict) -> tuple[int, int]:
    """(start, end) ns of the traced window, from its two marker spans."""
    marks = {n: s for n, s, _ in record["host_spans"]
             if n in ("window_start", "window_end")}
    if len(marks) != 2:
        raise RuntimeError(f"window markers missing from the trace: {marks}")
    return marks["window_start"], marks["window_end"]


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce(record: dict, top: int = 10) -> dict:
    """Window totals in seconds, averaged over the traced devices."""
    t0, t1 = window_of(record)
    devices = sorted({op[3] for op in record["device_ops"]})
    per_op = defaultdict(float)
    busy = 0.0
    gaps = []
    for dev in devices:
        intervals = []
        for name, start, dur, plane in record["device_ops"]:
            if plane != dev or name.split(".")[0] in CONTAINERS:
                continue
            s, e = max(start, t0), min(start + dur, t1)
            if e <= s:
                continue
            intervals.append((s, e))
            per_op[name] += (e - s) / 1e9
        merged = _union(intervals)
        busy += sum(e - s for s, e in merged) / 1e9
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    n_dev = max(len(devices), 1)
    spans = [(s, s + d, n) for n, s, d in record["host_spans"]
             if n in GAP_LABELS]
    by_label = defaultdict(float)
    for s, e in gaps:
        mid = (s + e) / 2
        inside = [sp for sp in spans if sp[0] <= mid < sp[1]]
        # the innermost span holds the gap: the one that started last
        label = GAP_LABELS[max(inside)[2]] if inside else "other"
        by_label[label] += (e - s) / 1e9 / n_dev
    ranked = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": (t1 - t0) / 1e9,
        "busy_s": busy / n_dev,
        "devices": len(devices),
        "device_ops": [[n, s / n_dev] for n, s in ranked],
        "idle_gaps": sorted(([k, v] for k, v in by_label.items()),
                            key=lambda kv: -kv[1])[:top],
    }
