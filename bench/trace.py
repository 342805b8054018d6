"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

From one traced window it computes the device's busy time (the union of
the intervals in which an operation ran, averaged over the devices), the
device time of every operation of every program inside the window, the
device time inside each of the harness's own host spans
(``jax.profiler.TraceAnnotation`` names that start with ``bench.``), and
the longest idle gaps of the device, each named by the innermost span
open on the host at the gap's midpoint, the harness's own (``bench.``) or
the program's (``repro.``, ``repro.telemetry``).

Planes: a device is a plane whose name matches ``device_plane`` (on a TPU
``/device:TPU:0``). Its operations are the events on its lines whose names
start with one of ``device_lines`` (``XLA Ops``), or on all its lines when
it has none of those; an operation is named by its HLO instruction name,
and its program by the ``XLA Modules`` event around it on the same device
(or, where a device has no such line, as on the CPU, by the event's
``hlo_module`` statistic). Operation times are clipped to the window and
split so that no instant counts twice: where operations nest or overlap,
each instant goes to the one that started last, so the times of all
operations add up to the busy time. Host spans are read from every line of
the ``/host:CPU`` plane.

``hlo_op_names`` reads a compiled program's HLO text (``Compiled.as_text()``)
into each instruction's ``op_name`` path, so that a reader can take the
operations of one scope (``jit(<function>)``) from the trace.
"""
from __future__ import annotations

import bisect
import glob
import heapq
import os
import re
from dataclasses import dataclass, field

import numpy as np

SPAN_PREFIX = "bench."
GAP_SPANS = (SPAN_PREFIX, "repro.")     # the spans that name idle gaps
WINDOW_SPAN = "bench.window"
MODULE_LINE = "XLA Modules"


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


def _self_times(ops: list, w0: float, w1: float) -> dict:
    """{key: ns} of ``ops`` [(start, end, key)] inside [w0, w1]: each
    instant of their union goes to the operation that started last among
    those running, so the times add up to the union's length."""
    pts = sorted([(s, 1, i) for i, (s, e, _) in enumerate(ops)
                  if e > w0 and s < w1]
                 + [(e, 0, i) for i, (s, e, _) in enumerate(ops)
                    if e > w0 and s < w1])
    running: list = []                  # heap of (-start, i)
    ended: set = set()
    out: dict = {}
    prev = None
    for t, starts, i in pts:
        while running and running[0][1] in ended:
            heapq.heappop(running)
        if running and prev is not None:
            lo, hi = max(prev, w0), min(t, w1)
            if hi > lo:
                key = ops[running[0][1]][2]
                out[key] = out.get(key, 0.0) + hi - lo
        if starts:
            heapq.heappush(running, (-ops[i][0], i))
        else:
            ended.add(i)
        prev = t
    return out


def _module_name(event_name: str) -> str:
    """``jit_decode_bs16(42)`` -> ``jit_decode_bs16``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def _stat(ev, name: str):
    return next((v for k, v in ev.stats if k == name), None)


_HLO_OP = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?'
                     r'metadata=\{[^}]*?op_name="([^"]*)"')


def hlo_op_names(hlo_text: str) -> tuple:
    """(module name, {instruction: op_name path}) of a compiled program's
    HLO text."""
    first = hlo_text.split("\n", 1)[0]
    module = first.split()[1].rstrip(",") if first.startswith("HloModule") \
        else ""
    names = {}
    for line in hlo_text.splitlines():
        m = _HLO_OP.match(line)
        if m:
            names[m.group(1)] = m.group(2)
    return module, names


@dataclass
class TraceSummary:
    """All times in seconds."""

    window_s: float
    busy_s: float                          # mean over devices
    n_devices: int
    device_ops: list = field(default_factory=list)      # [["mod:op", s]]
    module_ops: dict = field(default_factory=dict)      # {mod: {op: s}}
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


def _device_ops(plane, device_lines) -> list:
    """[(start, end, (module, op))] of one device plane's operations."""
    modules = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                      _module_name(ev.name))
                     for ln in plane.lines if ln.name == MODULE_LINE
                     for ev in ln.events)
    starts = [m[0] for m in modules]
    lines = [ln for ln in plane.lines if ln.name.startswith(device_lines)] \
        or [ln for ln in plane.lines
            if not ln.name.startswith(("Steps", MODULE_LINE))]
    ops = []
    for line in lines:
        for ev in line.events:
            if ev.duration_ns <= 0:
                continue
            t0 = ev.start_ns
            if modules:
                j = bisect.bisect_right(starts, t0) - 1
                module = modules[j][2] if j >= 0 and t0 < modules[j][1] \
                    else ""
            else:
                module = str(_stat(ev, "hlo_module") or "")
            # TPU op events carry the HLO text: keep its name
            name = ev.name.split(" = ", 1)[0].lstrip("%")
            ops.append((t0, t0 + ev.duration_ns, (module, name)))
    return ops


def reduce_trace(path: str, device_plane: str | None = None,
                 device_lines=None, host_plane: str | None = None,
                 top: int = 10) -> TraceSummary:
    from jax.profiler import ProfileData

    device_plane = device_plane or DEVICE_PLANE
    device_lines = tuple(device_lines or DEVICE_LINES)
    host_plane = host_plane or HOST_PLANE
    pd = ProfileData.from_file(path)
    dev_re = re.compile(device_plane)
    spans: list = []            # the harness's own
    named: list = []            # those that name idle gaps
    devices: list = []
    layout = []
    for plane in pd.planes:
        layout.append(f"{plane.name}: " + ", ".join(
            f"{ln.name}({sum(1 for _ in ln.events)})" for ln in plane.lines))
        if plane.name == host_plane:
            for line in plane.lines:
                for ev in line.events:
                    span = (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append(span)
                    if ev.name.startswith(GAP_SPANS) \
                            and ev.name != WINDOW_SPAN:
                        named.append(span)
        if dev_re.search(plane.name):
            devices.append(_device_ops(plane, device_lines))
    if not devices:
        raise ValueError(f"{path}: no plane matches {device_plane!r}")

    win = [s for s in spans if s[0] == WINDOW_SPAN]
    if win:
        w0, w1 = win[0][1], win[0][2]
    else:
        allv = [t for d in devices for op in d for t in op[:2]] or [0.0]
        w0, w1 = float(min(allv)), float(max(allv))
    busy_sets = [_clip(_union(np.asarray([op[:2] for op in d],
                                         dtype=np.float64).reshape(-1, 2)),
                       w0, w1) for d in devices]
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
    inner = sorted((e - s, name, s, e) for name, s, e in named)
    idle = []
    for g0, g1 in gaps[order]:
        mid = 0.5 * (g0 + g1)
        name = next((n for _, n, s, e in inner if s <= mid <= e), "host")
        idle.append([name, (g1 - g0) * 1e-9])

    # every operation's device time inside the window, per program
    scale = 1e-9 / len(devices)
    module_ops: dict = {}
    for d in devices:
        for (module, op), ns in _self_times(d, w0, w1).items():
            ops = module_ops.setdefault(module, {})
            ops[op] = ops.get(op, 0.0) + ns * scale
    flat = sorted(((f"{m}:{op}", v) for m, ops in module_ops.items()
                   for op, v in ops.items()), key=lambda kv: -kv[1])
    return TraceSummary(
        window_s=(w1 - w0) * 1e-9, busy_s=busy * 1e-9,
        n_devices=len(devices),
        device_ops=[[n, v] for n, v in flat[:top]], module_ops=module_ops,
        idle_gaps=idle, span_s=span_s, span_busy_s=span_busy,
        span_count=span_count, layout=layout)
