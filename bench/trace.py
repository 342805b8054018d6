"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

From one traced window it computes the device's busy time (the union of
the intervals in which an operation ran, averaged over the devices), the
device time by operation name, the device time inside each of the
harness's own host spans (``jax.profiler.TraceAnnotation`` names that
start with ``bench.``), and the longest idle gaps of the device, each
named by the innermost harness span that was open on the host at the
gap's midpoint.

Planes: a device is a plane whose name matches ``device_plane`` (on a TPU
``/device:TPU:0``). Its operations are the events on its lines whose names
start with one of ``device_lines`` (``XLA Ops``), or on all its lines when
it has none of those; an operation is named by its HLO instruction name.
Host spans are read from every line of the ``/host:CPU`` plane.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

import numpy as np

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _union(iv: np.ndarray) -> np.ndarray:
    """Merge (N, 2) [start, end) intervals into disjoint sorted ones."""
    if len(iv) == 0:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    out = []
    s, e = iv[0]
    for a, b in iv[1:]:
        if a > e:
            out.append((s, e))
            s, e = a, b
        elif b > e:
            e = b
    out.append((s, e))
    return np.asarray(out, dtype=np.float64)


def _clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    if len(iv) == 0:
        return iv.reshape(0, 2)
    c = np.stack([np.maximum(iv[:, 0], lo), np.minimum(iv[:, 1], hi)], 1)
    return c[c[:, 1] > c[:, 0]]


def _length(iv: np.ndarray) -> float:
    return float(np.sum(iv[:, 1] - iv[:, 0])) if len(iv) else 0.0


def _intersect_len(a: np.ndarray, b: np.ndarray) -> float:
    """Total length of the intersection of two disjoint sorted sets."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i, 0], b[j, 0]), min(a[i, 1], b[j, 1])
        if hi > lo:
            tot += hi - lo
        if a[i, 1] < b[j, 1]:
            i += 1
        else:
            j += 1
    return tot


@dataclass
class TraceSummary:
    """All times in seconds."""

    window_s: float
    busy_s: float                          # mean over devices
    n_devices: int
    device_ops: list = field(default_factory=list)      # [[name, s]] top
    idle_gaps: list = field(default_factory=list)       # [[span, s]] top
    span_s: dict = field(default_factory=dict)          # host span totals
    span_busy_s: dict = field(default_factory=dict)     # device busy inside
    span_count: dict = field(default_factory=dict)
    layout: list = field(default_factory=list)          # planes and lines

    @property
    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)


DEVICE_PLANE = r"^/device:(TPU|GPU):\d+$"
DEVICE_LINES = ("XLA Ops",)
HOST_PLANE = "/host:CPU"


def reduce_trace(path: str, device_plane: str | None = None,
                 device_lines=None, host_plane: str | None = None,
                 top: int = 10) -> TraceSummary:
    from jax.profiler import ProfileData

    device_plane = device_plane or DEVICE_PLANE
    device_lines = tuple(device_lines or DEVICE_LINES)
    host_plane = host_plane or HOST_PLANE
    pd = ProfileData.from_file(path)
    dev_re = re.compile(device_plane)
    spans: list = []
    devices: list = []
    by_name: dict = {}
    layout = []
    for plane in pd.planes:
        layout.append(f"{plane.name}: " + ", ".join(
            f"{ln.name}({sum(1 for _ in ln.events)})" for ln in plane.lines))
        if plane.name == host_plane:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
        if dev_re.search(plane.name):
            lines = [ln for ln in plane.lines
                     if ln.name.startswith(device_lines)] \
                or [ln for ln in plane.lines
                    if not ln.name.startswith(("Steps", "XLA Modules"))]
            iv = []
            for line in lines:
                for ev in line.events:
                    if ev.duration_ns <= 0:
                        continue
                    iv.append((ev.start_ns, ev.start_ns + ev.duration_ns))
                    # TPU op events carry the HLO text: keep its name
                    name = ev.name.split(" = ", 1)[0].lstrip("%")
                    by_name[name] = by_name.get(name, 0.0) + ev.duration_ns
            devices.append(np.asarray(iv, dtype=np.float64).reshape(-1, 2))
    if not devices:
        raise ValueError(f"{path}: no plane matches {device_plane!r}")

    win = [s for s in spans if s[0] == WINDOW_SPAN]
    if win:
        w0, w1 = win[0][1], win[0][2]
    else:
        allv = np.concatenate([d for d in devices if len(d)] or
                              [np.zeros((1, 2))])
        w0, w1 = float(allv[:, 0].min()), float(allv[:, 1].max())
    busy_sets = [_clip(_union(d), w0, w1) for d in devices]
    busy = float(np.mean([_length(b) for b in busy_sets]))

    span_iv: dict = {}
    for name, s, e in spans:
        if name != WINDOW_SPAN:
            span_iv.setdefault(name, []).append((s, e))
    span_s, span_busy, span_count = {}, {}, {}
    for name, iv in span_iv.items():
        u = _clip(_union(np.asarray(iv, dtype=np.float64)), w0, w1)
        span_s[name] = _length(u) * 1e-9
        span_busy[name] = float(np.mean([_intersect_len(b, u)
                                         for b in busy_sets])) * 1e-9
        span_count[name] = len(iv)

    # idle gaps of the first device, named by the innermost open span
    b = busy_sets[0]
    edges = np.concatenate([[w0], b.ravel(), [w1]]).reshape(-1, 2)
    gaps = edges[edges[:, 1] > edges[:, 0]]
    order = np.argsort(gaps[:, 0] - gaps[:, 1])[:top]
    inner = sorted((e - s, name, s, e) for name, s, e in spans
                   if name != WINDOW_SPAN)
    idle = []
    for g0, g1 in gaps[order]:
        mid = 0.5 * (g0 + g1)
        name = next((n for _, n, s, e in inner if s <= mid <= e), "host")
        idle.append([name, (g1 - g0) * 1e-9])

    ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    return TraceSummary(
        window_s=(w1 - w0) * 1e-9, busy_s=busy * 1e-9,
        n_devices=len(devices),
        device_ops=[[n, v * 1e-9 / len(devices)] for n, v in ops],
        idle_gaps=idle, span_s=span_s, span_busy_s=span_busy,
        span_count=span_count, layout=layout)
