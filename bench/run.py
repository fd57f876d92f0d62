"""Run one benchmark cell once on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's configuration and traffic (``BENCHMARK.json`` and the
files it names, see ``bench/harness.py``), makes its inputs from the seed,
warms up, measures for ``--seconds`` seconds, checks the timed path's
results against the plain reference and prints one JSON line as the last
line of standard output. ``--trace 0`` reports the cell's end-to-end
metrics; ``--trace 1`` traces a shorter window (the traffic's
``trace_seconds``) with the profiler and reports the per-layer metrics,
the device's busy time and a breakdown.

Without a TPU, or with fewer chips than the cell asks for, it exits with
code 2 and prints no result. The program under test is imported from
``src/`` beside ``bench/``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
# the TPU runtime would otherwise log to a fixed path under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import harness  # noqa: E402
from harness import Check, CompileWatch, eprint, now  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()[:chips]
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def execute(args, reg: harness.Registry, peaks=None) -> int:
    """Everything after the look for a chip: set-up, window, metrics,
    check, result line. A test that passes ``peaks`` skips that look."""
    cell = reg.cell(args.workload)
    cfg = reg.config(cell["config"])
    traffic = reg.traffic(cell["traffic"])
    import jax
    if peaks is None:
        if jax.default_backend() != "tpu":
            eprint(f"bench: no TPU (JAX backend is {jax.default_backend()!r})")
            return 2
        if len(jax.devices()) < cell["chips"]:
            eprint(f"bench: {cell['name']} needs {cell['chips']} chips, "
                   f"found {len(jax.devices())}")
            return 2
        peaks = harness.peaks_for(jax.devices()[0].device_kind)
    harness.use_compile_cache(reg.root)
    src = os.path.join(reg.root, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    tracing = bool(args.trace)
    runner = reg.runner(cfg["runner"])
    run = runner.Run(cfg, traffic, args.seed, reg.reference(cell["config"]),
                     tracing)
    with CompileWatch() as setup_watch:
        run.setup()
    setup_s = now() - T_START
    trace_dir = None
    with CompileWatch() as window_watch:
        if tracing:
            trace_dir = os.path.join(reg.root, ".bench_trace", args.workload)
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(
                trace_dir, profiler_options=harness.profile_options())
            try:
                with jax.profiler.TraceAnnotation("bench.window"):
                    run.window(min(args.seconds, traffic["trace_seconds"]))
            finally:
                jax.profiler.stop_trace()
        else:
            run.window(args.seconds)
    device = device_info(cell["chips"])
    e2e = run.end_to_end()
    counters = run.counters() if tracing else {}
    run.release()
    gaps = run.compare()
    limits = reg.limits(cell["config"])
    checks = [Check("window_compiles", window_watch.compiles, 0)]
    checks += [Check(k, gaps[k], limits[k]) for k in limits]
    correct = all(c.ok for c in checks)

    metrics, breakdown = {}, None
    if tracing:
        import trace_reduce
        reduced = trace_reduce.reduce_dir(trace_dir, chips=cell["chips"])
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        breakdown = reduced.breakdown()
        ctx = harness.RunContext(cell, cfg, traffic, peaks, counters, reduced)
        for m in reg.per_layer(args.workload):
            value = reg.reader(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        e2e["setup_s"] = setup_s
        for m in reg.end_to_end(args.workload):
            if m["name"] not in e2e:
                raise KeyError(f"cell {args.workload!r} measured no "
                               f"{m['name']!r}")
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    eprint(f"setup: {setup_s!r} s, {setup_watch.compiles} traced/compiled, "
           f"cache hits {setup_watch.cache_hits}, misses "
           f"{setup_watch.cache_misses}; window: "
           f"{window_watch.compiles} traced/compiled")
    for k in sorted(set(gaps) - set(limits)):
        eprint(f"reading {k}: {gaps[k]!r} (not compared)")
    for c in checks:
        eprint(c.line())
    print(harness.result_line(correct, run.attempted, run.failed, metrics,
                              device, checks, breakdown), flush=True)
    return 0


def main(argv=None) -> int:
    args = parse(argv)
    return execute(args, harness.Registry())


if __name__ == "__main__":
    sys.exit(main())
