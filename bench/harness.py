"""What every cell shares: name resolution, compile watching, results.

The benchmark is driven by data. ``BENCHMARK.json`` lists the cells
(``workloads``), the configurations and the metrics; everything else is
found by name:

* a configuration's sizes: the ``file`` of its ``configs`` entry, a JSON
  object whose ``runner`` names ``bench/runners/<runner>.py``, with its
  plain reference beside it as ``<file stem>_ref.py`` and the limits of
  its comparison as ``<file stem>.limits.json``;
* a traffic mix: ``bench/traffic/<traffic>.json``, read by the runner's
  generator;
* a per-layer metric: ``bench/metrics/<name>.py``, whose ``read(ctx)``
  returns the number or None; a quantity split by the end-to-end metric
  it moves (``<base>.<part>``) may share ``bench/metrics/<base>.py``.

A later cell, mix, configuration or metric is added as files and entries
alone.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import os
import sys
import time
from typing import Any, Dict, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)

# events of JAX's own monitoring that mean "something was traced, lowered
# or compiled" (a persistent-cache hit still reports a backend compile)
COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
CACHE_EVENTS = ("/jax/compilation_cache/cache_hits",
                "/jax/compilation_cache/cache_misses")


def load_module(path: str, name: Optional[str] = None):
    """Import a file by path (metric readers, runners, references)."""
    name = name or os.path.splitext(os.path.basename(path))[0].replace(
        ".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _read_json(path: str):
    with open(path) as f:
        return json.load(f)


class Registry:
    """``BENCHMARK.json`` and the files it names, under one root."""

    def __init__(self, root: str = ROOT):
        self.root = root
        self.bench = os.path.join(root, "bench")
        self.spec = _read_json(os.path.join(root, "BENCHMARK.json"))

    def _entry(self, key: str, name: str) -> dict:
        for e in self.spec[key]:
            if e["name"] == name:
                return e
        known = ", ".join(e["name"] for e in self.spec[key])
        raise KeyError(f"no {key} entry named {name!r} (known: {known})")

    def cell(self, name: str) -> dict:
        return self._entry("workloads", name)

    def config(self, name: str) -> dict:
        cfg = _read_json(os.path.join(self.root,
                                      self._entry("configs", name)["file"]))
        if cfg.get("name") != name:
            raise ValueError(f"config file of {name!r} names "
                             f"{cfg.get('name')!r}")
        return cfg

    def _config_stem(self, name: str) -> str:
        return os.path.splitext(os.path.join(
            self.root, self._entry("configs", name)["file"]))[0]

    def reference(self, config: str):
        return load_module(self._config_stem(config) + "_ref.py")

    def limits(self, config: str) -> Dict[str, float]:
        return _read_json(self._config_stem(config) + ".limits.json")

    def traffic(self, name: str) -> dict:
        return _read_json(os.path.join(self.bench, "traffic", f"{name}.json"))

    def runner(self, kind: str):
        return load_module(os.path.join(self.bench, "runners", f"{kind}.py"),
                           f"bench_runner_{kind}")

    def _for_cell(self, key: str, cell: str) -> List[dict]:
        return [m for m in self.spec[key]
                if "workloads" not in m or cell in m["workloads"]]

    def end_to_end(self, cell: str) -> List[dict]:
        return self._for_cell("end_to_end", cell)

    def per_layer(self, cell: str) -> List[dict]:
        return self._for_cell("per_layer", cell)

    def reader(self, metric: str):
        """The reader of a per-layer metric: ``metrics/<name>.py``, else
        ``metrics/<base>.py`` for a name ``<base>.<part>``."""
        path = os.path.join(self.bench, "metrics", f"{metric}.py")
        if not os.path.exists(path):
            path = os.path.join(self.bench, "metrics",
                                metric.split(".")[0] + ".py")
        return load_module(path, "bench_metric_" + metric.replace(".", "_"))


def peaks_for(kind: str, path: str = os.path.join(BENCH, "peaks.json")):
    """The published peaks of a ``device_kind``; an unknown kind is an
    error, never a default."""
    table = _read_json(path)
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in {path} "
                       f"(known: {sorted(table)})")
    return table[kind]


def seed_key(seed: int):
    """A PRNG key from any non-negative seed (PRNGKey keeps 32 bits)."""
    import jax
    if seed < 0:
        raise ValueError("seeds are non-negative")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


class CompileWatch:
    """Counts traces, lowerings and compiles, and persistent-cache hits and
    misses, while it is entered."""

    def __init__(self):
        self.compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0

    def _on_duration(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            self.compiles += 1

    def _on_event(self, event, **_):
        if event == CACHE_EVENTS[0]:
            self.cache_hits += 1
        elif event == CACHE_EVENTS[1]:
            self.cache_misses += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        from jax._src import monitoring
        monitoring.unregister_event_duration_listener(self._on_duration)
        monitoring.unregister_event_listener(self._on_event)


def use_compile_cache(root: str = ROOT) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says), every program kept."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def spans(tracing: bool):
    """``span(name)`` for the harness's host spans: a profiler annotation
    in a traced run, a no-op otherwise."""
    if not tracing:
        null = contextlib.nullcontext()
        return lambda name: null
    import jax
    return jax.profiler.TraceAnnotation


def profile_options():
    """Profiler options of a traced window: device ops and the harness's
    own host spans, without the Python function tracer (which records
    every call of a host-bound service and multiplies the trace)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


class Check:
    """One number compared beside its limit (at most ``limit`` passes)."""

    def __init__(self, name: str, value: float, limit: float):
        self.name, self.value, self.limit = name, float(value), float(limit)

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit

    def line(self) -> str:
        return (f"check {self.name}: {self.value!r} limit {self.limit!r} "
                f"{'ok' if self.ok else 'FAILED'}")


class RunContext:
    """What a per-layer reader sees of one traced run."""

    def __init__(self, cell: dict, config: dict, traffic: dict,
                 peaks: dict, counters: Dict[str, Any], trace):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.peaks = peaks
        self.counters = counters
        self.trace = trace        # trace_reduce.Reduced or None


def result_line(correct: bool, attempted: int, failed: int,
                metrics: Dict[str, dict], device: dict,
                checks: List[Check], breakdown=None) -> str:
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in checks}
    return json.dumps(out)


def now() -> float:
    return time.perf_counter()


def eprint(*a) -> None:
    print(*a, file=sys.stderr, flush=True)
