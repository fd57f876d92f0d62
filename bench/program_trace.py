"""From a profiler trace to what the program's own names say: device
seconds per named scope, and per program span its count, self time and
the device's idle time inside that self time.

It reads the same ``.xplane.pb`` as ``bench/trace_reduce.py`` and keeps
its window (the harness span ``bench.window``), its device planes and
its ``XLA Ops`` lines:

* ``scope_s``: each non-container operation's device time, clipped to
  the window, goes to the innermost named scope in its HLO ``op_name``;
  scopes are path parts that start with ``fl.``, and an operation under
  none goes to ``other``. Averaged over devices. A TPU's ``XLA Ops``
  events carry no ``op_name``: it is looked up by the operation's name in
  the HLO of the module running at that moment (the device plane's
  ``XLA Modules`` line), which the trace keeps as an ``Hlo Proto`` stat
  per module on its ``/host:metadata`` plane, read here from the raw
  protocol buffer.
* ``program_spans``: host events whose name (before any ``#``) starts
  with ``service.`` or ``fl.`` and that start inside the window. A span's
  self time is its stretch of the window less what the program spans
  nested in it on the same host thread cover; its idle time is the part
  of that self time in which no operation ran on the device (averaged
  over devices).

    python bench/program_trace.py <trace dir or .xplane.pb> [chips]

prints both as one JSON line.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys
from typing import Dict, List, Optional

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import trace_reduce as tr  # noqa: E402

SPAN_PREFIXES = ("service.", "fl.")
SCOPE = re.compile(r"(?:^|[/(])(fl\.[A-Za-z_][\w.]*)")
OTHER = "other"
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"
MODULES_LINE = "XLA Modules"


def span_name(event_name: str) -> str:
    """``service.pull#flush=3#`` -> ``service.pull``."""
    return event_name.split("#", 1)[0]


def scope_of(op_path: str) -> str:
    """The innermost named scope of an HLO ``op_name`` path."""
    found = SCOPE.findall(op_path)
    return found[-1] if found else OTHER


def module_name(event_name: str) -> str:
    """``jit__run_chunk(1234)`` -> ``jit__run_chunk``."""
    return event_name.split("(", 1)[0]


# ---------------------------------------------------------------- protobuf
# The few fields read here (xplane.proto, hlo.proto, xla_data.proto):
# XSpace.planes 1; XPlane.name 2, .event_metadata 4, .stat_metadata 5
# (map entries: key 1, value 2); XEventMetadata.name 2, .stats 5;
# XStatMetadata.id 1, .name 2; XStat.metadata_id 1, .bytes_value 6;
# HloProto.hlo_module 1; HloModuleProto.computations 3;
# HloComputationProto.instructions 2; HloInstructionProto.name 1,
# .metadata 7; OpMetadata.op_name 2.

def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one message: varints as ints,
    length-delimited values as memoryviews, fixed widths skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            i += 8 if kind == 1 else 4
            continue
        else:
            raise ValueError(f"protobuf wire type {kind} is not read here")
        yield key >> 3, v


def _field(buf, number: int, default=None):
    for k, v in _fields(buf):
        if k == number:
            return v
    return default


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace") if v is not None else ""


def hlo_op_names(hlo_proto) -> Dict[str, str]:
    """An ``HloProto``'s instructions -> their ``op_name``."""
    out = {}
    module = _field(hlo_proto, 1, b"")
    for k, comp in _fields(module):
        if k != 3:
            continue
        for k2, inst in _fields(comp):
            if k2 != 2:
                continue
            name = meta = None
            for k3, v in _fields(inst):
                if k3 == 1:
                    name = _text(v)
                elif k3 == 7:
                    meta = v
            if name and meta is not None:
                out[name] = _text(_field(meta, 2))
    return out


def module_op_names(path: str) -> Dict[str, Dict[str, str]]:
    """Module name (before any ``(``) -> instruction -> ``op_name``, from
    the ``Hlo Proto`` stats of a trace's ``/host:metadata`` plane."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: Dict[str, Dict[str, str]] = {}
    for k, plane in _fields(space):
        if k != 1 or _text(_field(plane, 2)) != METADATA_PLANE:
            continue
        stat_ids = set()
        for k2, entry in _fields(plane):
            if k2 == 5:
                meta = _field(entry, 2, b"")
                if _text(_field(meta, 2)) == HLO_PROTO_STAT:
                    stat_ids.add(_field(meta, 1, 0))
        for k2, entry in _fields(plane):
            if k2 != 4:
                continue
            meta = _field(entry, 2, b"")
            name = module_name(_text(_field(meta, 2)))
            for k3, stat in _fields(meta):
                if k3 == 5 and _field(stat, 1) in stat_ids:
                    out.setdefault(name, {}).update(
                        hlo_op_names(_field(stat, 6, b"")))
    return out


def idle_before(g_lo: np.ndarray, g_hi: np.ndarray):
    """For sorted disjoint idle gaps, ``f(x)``: the idle length before
    ``x`` (so the idle time inside [a, b) is ``f(b) - f(a)``)."""
    if g_lo.size == 0:
        return lambda x: np.zeros(np.shape(x))
    cum = np.concatenate(([0.0], np.cumsum(g_hi - g_lo)))

    def f(x):
        x = np.asarray(x, np.float64)
        last = np.searchsorted(g_lo, x, side="right") - 1   # gap begun
        j = np.maximum(last, 0)
        part = np.clip(np.minimum(x, g_hi[j]) - g_lo[j], 0.0, None)
        return np.where(last >= 0, cum[j] + part, 0.0)

    return f


def self_intervals(spans: List[tuple]) -> List[tuple]:
    """``(name, start, end)`` spans of one thread, properly nested ->
    ``(name, [(lo, hi), ...])``: each span's stretches not covered by the
    spans directly inside it."""
    order = sorted(range(len(spans)), key=lambda i: (spans[i][1],
                                                     -spans[i][2]))
    children: Dict[int, List[int]] = {i: [] for i in order}
    stack: List[int] = []
    for i in order:
        _, s, e = spans[i]
        while stack and spans[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= spans[stack[-1]][2]:
            children[stack[-1]].append(i)
        stack.append(i)
    out = []
    for i in order:
        name, s, e = spans[i]
        kids = children[i]
        if kids:
            k_lo = np.array([spans[k][1] for k in kids], np.float64)
            k_hi = np.array([spans[k][2] for k in kids], np.float64)
            pieces = list(zip(*tr.gaps(k_lo, k_hi, s, e)))
        else:
            pieces = [(s, e)]
        out.append((name, pieces))
    return out


def _scoped_ops(ops, modules, op_names) -> List[tuple]:
    """A device's operations as ``(text, scope, start, duration)``; each
    operation's ``op_name`` comes from the module running at its start."""
    m_start = np.array([e.start_ns for e in modules], np.float64)
    m_name = [module_name(e.name) for e in modules]
    out = []
    for e in ops:
        i = int(np.searchsorted(m_start, e.start_ns, side="right")) - 1
        names = op_names.get(m_name[i], {}) if i >= 0 else {}
        path = names.get(tr.op_name(e.name), "")
        out.append((e.name, scope_of(path), e.start_ns, e.duration_ns))
    return out


class ProgramTrace:
    """The program's names in one traced window. Times in seconds."""

    def __init__(self, scope_s: Dict[str, float],
                 program_spans: Dict[str, Dict[str, float]], devices: int):
        self.scope_s = scope_s              # device seconds per scope
        self.program_spans = program_spans  # name -> count, self_s, idle_s
        self.devices = devices

    def as_dict(self) -> dict:
        return {"scope_s": self.scope_s, "program_spans": self.program_spans,
                "devices": self.devices}


def reduce_file(path: str, chips: int = 1) -> ProgramTrace:
    """Reduce one ``.xplane.pb`` file (see the module docstring)."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    op_names = module_op_names(path)
    threads: List[List[tuple]] = []
    window: Optional[tuple] = None
    device_lines = []
    for plane in pd.planes:
        m = tr.DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) < chips:
            lines = {line.name: list(line.events) for line in plane.lines}
            if tr.OPS_LINE in lines:
                device_lines.append(_scoped_ops(
                    lines[tr.OPS_LINE], lines.get(MODULES_LINE, []),
                    op_names))
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                spans = []
                for e in line.events:
                    name = span_name(e.name)
                    if name == tr.WINDOW_SPAN:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
                    elif name.startswith(SPAN_PREFIXES):
                        spans.append((name, e.start_ns,
                                      e.start_ns + e.duration_ns))
                if spans:
                    threads.append(spans)
    if window is None:
        raise ValueError(f"{path}: no {tr.WINDOW_SPAN!r} span")
    if not device_lines:
        raise ValueError(f"{path}: no {tr.OPS_LINE!r} line on a TPU plane")
    lo, hi = window
    scope_s: Dict[str, float] = {}
    idle_fns = []
    for evs in device_lines:
        starts = np.array([s for _, _, s, _ in evs], np.float64)
        ends = starts + np.array([d for _, _, _, d in evs], np.float64)
        inside = (ends > lo) & (starts < hi)
        s_in = np.clip(starts[inside], lo, hi)
        e_in = np.clip(ends[inside], lo, hi)
        kept = [ev for ev, k in zip(evs, inside) if k]
        for (name, key, _, _), s, e in zip(kept, s_in, e_in):
            if tr.is_container(name):
                continue
            scope_s[key] = scope_s.get(key, 0.0) + (e - s) * 1e-9
        idle_fns.append(idle_before(*tr.gaps(s_in, e_in, lo, hi)))
    n = len(device_lines)
    program_spans: Dict[str, Dict[str, float]] = {}
    for spans in threads:
        clipped = [(name, max(s, lo), min(e, hi)) for name, s, e in spans
                   if lo <= s < hi]
        for name, pieces in self_intervals(clipped):
            a = np.array([p[0] for p in pieces], np.float64)
            b = np.array([p[1] for p in pieces], np.float64)
            idle = sum(float(np.sum(f(b) - f(a))) for f in idle_fns) / n
            rec = program_spans.setdefault(
                name, {"count": 0, "self_s": 0.0, "idle_s": 0.0})
            rec["count"] += 1
            rec["self_s"] += float(np.sum(b - a)) * 1e-9
            rec["idle_s"] += idle * 1e-9
    return ProgramTrace(scope_s={k: v / n for k, v in scope_s.items()},
                        program_spans=program_spans, devices=n)


def newest_trace(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` under a ``start_trace`` directory."""
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    path = argv[0]
    if os.path.isdir(path):
        path = newest_trace(path)
    chips = int(argv[1]) if len(argv) > 1 else 1
    print(json.dumps(reduce_file(path, chips).as_dict()))
    return 0


if __name__ == "__main__":
    sys.exit(main())
