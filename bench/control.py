"""Readings that the comparison's limits are set from (not run by the
benchmark's runs).

    python bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3

For each seed, in this one process: the cell's set-up and a window of
``--seconds`` seconds as a run makes them, then the comparison's numbers
for the program against the plain reference (the lower readings), for the
control, the reference computed one precision below the configuration's
(bfloat16 for float32) and put in the program's place (the upper
readings), and, for an engine cell, for each planted fault of the
reference in the program's place. One JSON line per seed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import harness  # noqa: E402

ENGINE_FAULTS = ("frozen", "half_batch", "flip")


def readings(reg: harness.Registry, workload: str, seed: int,
             seconds: float) -> dict:
    cell = reg.cell(workload)
    cfg = reg.config(cell["config"])
    traffic = reg.traffic(cell["traffic"])
    run = reg.runner(cfg["runner"]).Run(
        cfg, traffic, seed, reg.reference(cell["config"]), tracing=False)
    run.setup()
    run.window(seconds)
    run.release()
    out = {"seed": seed, "program": run.compare(),
           "control": run.compare(control=True)}
    if cfg["runner"] == "engine":
        out["faults"] = {f: run.compare(fault=f) for f in ENGINE_FAULTS}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    import jax
    if jax.default_backend() != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 2
    reg = harness.Registry()
    harness.use_compile_cache(reg.root)
    sys.path.insert(0, os.path.join(reg.root, "src"))
    for seed in args.seeds:
        print(json.dumps(readings(reg, args.workload, seed, args.seconds)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
