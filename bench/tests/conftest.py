"""Shared fixtures of the benchmark's own tests (``pytest bench/tests``).

They run on the CPU at tiny sizes; nothing here needs a chip."""

import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_ENGINE = {
    "name": "tiny_engine", "runner": "engine",
    "source": "https://arxiv.org/abs/2201.07912", "precision": "float32",
    "n_clients": 8, "sigma_fracs": [0.1, 0.4, 0.5],
    "sigma_values": [0.2, 0.75, 1.2], "height": 8, "width": 8,
    "channels": 3, "n_classes": 10, "conv1": 4, "conv2": 8, "hidden": 16,
    "ksize": 5, "batch": 4, "local_steps": 2, "gamma": 0.01,
    "bandwidth_hz": 22e6, "noise_power": 1.0, "p_max": 100.0, "p_bar": 1.0,
    "d_paper": 555178, "model_bits": 17765696.0, "V": 1000.0, "lam": 10.0,
    "q_floor": 1e-5, "guarantee_one": True, "per_client": 16, "n_test": 32,
    "solver": "jnp", "m_cap": 4, "eval_every": 2, "eval_size": 16,
    "assumed": {}, "reduced": {}, "memory": "tiny"}

TINY_SERVICE = {
    "name": "tiny_service", "runner": "service",
    "source": "https://arxiv.org/abs/2201.07912", "precision": "float32",
    "solver": "jnp", "policy": "proposed", "V": 1000.0,
    "bandwidth_hz": 22e6, "noise_power": 1.0, "p_max": 100.0, "p_bar": 1.0,
    "max_spectral_eff": 10.0, "min_spectral_eff": 0.25, "q_floor": 1e-5,
    "guarantee_one": True,
    "tenants": [
        {"name": f"{w}-{lam}", "n_clients": n, "model_bits": bits,
         "lam": lam, "sigma_counts": counts,
         "sigma_values": [0.2, 0.75, 1.2][:len(counts)]}
        for w, n, bits, counts in (("a", 5, 17765696.0, [1, 2, 2]),
                                   ("b", 40, 14209984.0, [4, 16, 20]))
        for lam in (10.0, 100.0)],
    "assumed": {}, "reduced": {}, "memory": "tiny"}

TINY_TRAFFIC = {
    "tiny_rounds": {"kind": "engine_chunks", "chunk_rounds": 1,
                    "setup_chunks": 3, "trace_seconds": 1},
    "tiny_flushes": {"kind": "tenant_flushes", "flush_size": "each",
                     "payloads_per_tenant": 4, "setup_flushes": 4,
                     "warmup_max_batch": 1, "trace_seconds": 1},
    "tiny_flush_all": {"kind": "tenant_flushes", "flush_size": "all",
                       "payloads_per_tenant": 4, "setup_flushes": 2,
                       "warmup_max_batch": 0, "trace_seconds": 1},
}

# The tiny engine runs in float32 on the CPU, where the default precision
# is full float32, so its readings lie below the chip's (where program
# and reference round their matmuls to one bfloat16 pass); its limits
# keep the chip's keys at the CPU's scale: program readings about 1e-8
# to 1e-7, control and faults 1e-4 and up.
TINY_ENGINE_LIMITS = {
    "z_gap": 1e-5, "t_comm_gap": 1e-5, "power_gap": 1e-5,
    "first_update_gap": 1e-5, "first_update_median_gap": 1e-5,
    "param_change_gap": 1e-5,
    "key_mismatch": 0}


@pytest.fixture
def tiny_root(tmp_path):
    """A copy of the benchmark with two tiny cells added as files and
    entries only: configurations (sizes, reference, limits), traffic mixes
    and ``BENCHMARK.json`` entries."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(os.path.join(ROOT, "src"), root / "src")
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    configs = root / "bench" / "configs"
    for cfg, real in ((TINY_ENGINE, "cifar10_vi_a"),
                      (TINY_SERVICE, "tenants_paper_grid")):
        (configs / f"{cfg['name']}.json").write_text(json.dumps(cfg))
        shutil.copy(configs / f"{real}_ref.py",
                    configs / f"{cfg['name']}_ref.py")
        shutil.copy(configs / f"{real}.limits.json",
                    configs / f"{cfg['name']}.limits.json")
        spec["configs"].append({
            "name": cfg["name"], "source": cfg["source"],
            "file": f"bench/configs/{cfg['name']}.json", "reduced": [],
            "why": "tiny test size"})
    (configs / "tiny_engine.limits.json").write_text(
        json.dumps(TINY_ENGINE_LIMITS))
    for name, tr in TINY_TRAFFIC.items():
        (root / "bench" / "traffic" / f"{name}.json").write_text(
            json.dumps(tr))
    for cell, cfg, tr, like in (
            ("tiny_engine_cell", "tiny_engine", "tiny_rounds", "engine_vi_a"),
            ("tiny_service_cell", "tiny_service", "tiny_flushes",
             "service_smallflush"),
            ("tiny_service_all_cell", "tiny_service", "tiny_flush_all",
             "service_full")):
        spec["workloads"].append({"name": cell, "config": cfg,
                                  "traffic": tr, "chips": 1,
                                  "why": "tiny test size"})
        for m in spec["end_to_end"] + spec["per_layer"]:
            if like in m.get("workloads", ()):
                m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return str(root)


CPU_PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def run_cell(root, cell, capsys, seed=5, seconds=0.5):
    """Drive one run of ``cell`` from ``root`` on the CPU (the look for a
    chip skipped) -> (exit code, result line)."""
    import harness
    import run as bench_run
    args = bench_run.parse(["--workload", cell, "--seed", str(seed),
                            "--seconds", str(seconds), "--trace", "0"])
    rc = bench_run.execute(args, harness.Registry(root), peaks=CPU_PEAKS)
    lines = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(lines[-1])
