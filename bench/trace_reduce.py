"""From a profiler trace (``.xplane.pb``) to the numbers the benchmark
reports: device busy time and idle share over the traced window, device
time per operation and per kernel, and the idle gaps labelled by what the
harness was doing on the host.

Read with ``jax.profiler.ProfileData``: a device is a plane named
``/device:TPU:<i>``, its operations are the events of its ``XLA Ops``
line (control-flow containers such as a ``while`` hold the operations
of their body and are counted as busy time only through them). The
harness's own host spans (``jax.profiler.TraceAnnotation``) sit on the
host plane ``/host:CPU``; the window is the span named ``bench.window``.
Host and device events share the trace's clock.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional

import numpy as np

WINDOW_SPAN = "bench.window"
HOST_SPANS = ("chunk", "flush", "submit")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
CONTAINERS = ("while", "conditional", "call")


def op_name(event_name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    head = event_name.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def is_container(event_name: str) -> bool:
    base = op_name(event_name).split(".", 1)[0]
    return base in CONTAINERS


def union_length(starts: np.ndarray, ends: np.ndarray) -> float:
    """Total length covered by the union of intervals [start, end)."""
    if starts.size == 0:
        return 0.0
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    reach = np.maximum.accumulate(e)
    prev = np.concatenate(([-np.inf], reach[:-1]))
    return float(np.sum(np.maximum(0.0, e - np.maximum(s, prev))))


def gaps(starts: np.ndarray, ends: np.ndarray, lo: float, hi: float):
    """The uncovered stretches of [lo, hi]: -> (gap starts, gap ends)."""
    if starts.size == 0:
        return np.array([lo]), np.array([hi])
    order = np.argsort(starts, kind="stable")
    reach = np.maximum.accumulate(ends[order])
    g_lo = np.concatenate(([lo], reach))
    g_hi = np.concatenate((starts[order], [hi]))
    keep = g_hi > g_lo
    return g_lo[keep], g_hi[keep]


def label_gaps(g_lo, g_hi, spans) -> Dict[str, float]:
    """Idle nanoseconds per host span that covers each gap's midpoint
    (spans do not overlap; a gap outside every span is ``other``)."""
    out: Dict[str, float] = {}
    spans = sorted(spans, key=lambda s: s[1])
    s_start = np.array([s[1] for s in spans], np.float64)
    s_end = np.array([s[2] for s in spans], np.float64)
    mids = (g_lo + g_hi) / 2
    idx = np.searchsorted(s_start, mids, side="right") - 1
    for i, a, b, mid in zip(idx, g_lo, g_hi, mids):
        label = spans[i][0] if i >= 0 and mid < s_end[i] else "other"
        out[label] = out.get(label, 0.0) + (b - a)
    return out


class Reduced:
    """The reduction of one traced window. Times in seconds."""

    def __init__(self, window_s: float, busy_s: float,
                 op_s: Dict[str, float], text_s: Dict[str, float],
                 idle_by_span: Dict[str, float], devices: int):
        self.window_s = window_s
        self.busy_s = busy_s
        self.op_s = op_s            # device seconds per operation name
        self.text_s = text_s        # ... per full operation text
        self.idle_by_span = idle_by_span
        self.devices = devices

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def kernel_s(self, pattern: str) -> float:
        """Device seconds of the operations whose text matches
        ``pattern`` (a regular expression over the full event name, kept
        of the operations in ``text_s``)."""
        rx = re.compile(pattern)
        return sum(t for text, t in self.text_s.items() if rx.search(text))

    def breakdown(self) -> dict:
        top = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:10]
        idle = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in idle]}


def _events(line):
    return [(e.name, e.start_ns, e.duration_ns) for e in line.events]


def reduce_file(path: str, chips: int = 1) -> Reduced:
    """Reduce one ``.xplane.pb`` file (see the module docstring)."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    host_spans: List[tuple] = []
    window: Optional[tuple] = None
    device_lines = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) < chips:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_lines.append(_events(line))
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for name, start, dur in _events(line):
                    if name == WINDOW_SPAN:
                        window = (start, start + dur)
                    elif name in HOST_SPANS:
                        host_spans.append((name, start, start + dur))
    if window is None:
        raise ValueError(f"{path}: no {WINDOW_SPAN!r} span")
    if not device_lines:
        raise ValueError(f"{path}: no {OPS_LINE!r} line on a TPU plane")
    lo, hi = window
    busy, op_s, text_s = 0.0, {}, {}
    idle_by_span: Dict[str, float] = {}
    for evs in device_lines:
        starts = np.array([s for _, s, _ in evs], np.float64)
        ends = starts + np.array([d for _, _, d in evs], np.float64)
        inside = (ends > lo) & (starts < hi)
        s_in = np.clip(starts[inside], lo, hi)
        e_in = np.clip(ends[inside], lo, hi)
        busy += union_length(s_in, e_in)
        for (name, _, _), s, e in zip(
                (ev for ev, k in zip(evs, inside) if k), s_in, e_in):
            if is_container(name):
                continue
            key = op_name(name)
            op_s[key] = op_s.get(key, 0.0) + (e - s) * 1e-9
            text_s[name] = text_s.get(name, 0.0) + (e - s) * 1e-9
        g_lo, g_hi = gaps(s_in, e_in, lo, hi)
        for k, v in label_gaps(g_lo, g_hi, host_spans).items():
            idle_by_span[k] = idle_by_span.get(k, 0.0) + v * 1e-9
    n = len(device_lines)
    return Reduced(window_s=(hi - lo) * 1e-9, busy_s=busy * 1e-9 / n,
                   op_s={k: v / n for k, v in op_s.items()},
                   text_s={k: v / n for k, v in text_s.items()},
                   idle_by_span={k: v / n for k, v in idle_by_span.items()},
                   devices=n)


def reduce_dir(trace_dir: str, chips: int = 1) -> Reduced:
    """Reduce the newest ``.xplane.pb`` under a ``start_trace`` directory."""
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce_file(max(files, key=os.path.getmtime), chips)
