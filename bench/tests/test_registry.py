"""``BENCHMARK.json`` against the rules it keeps, and cells, mixes
and metrics added as files and entries only."""

import json
import os
import re
import subprocess
import sys

import pytest

import harness
from conftest import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["paths"] == ["bench"]
    assert spec["command"] == ["python3", "bench/run.py"]
    assert 1 <= spec["run_seconds"] <= 51
    cells = len(spec["workloads"])
    # a full check: 2 + 14 runs per cell, each run_seconds + 60, two
    # compiles of 90 s per cell and 1200 s spare, for the full 24 cells
    assert (2 + 14 * 24) * (spec["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200
    assert 1 <= cells <= 24


def test_entries(spec):
    names = set()
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/")
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        names.add(c["name"])
    used = set()
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert w["config"] in names and len(w["why"]) <= 200
        assert os.path.exists(os.path.join(BENCH, "traffic",
                                           w["traffic"] + ".json"))
        used.add(w["config"])
    assert used == names
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in e2e
    for m in spec["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25 and UNIT.match(m["unit"])
    for m in spec["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and m["moves"] in e2e
        assert harness.Registry().reader(m["name"]).read


def test_every_cell_reports_setup_another_metric_and_a_layer(spec):
    reg = harness.Registry()
    for w in spec["workloads"]:
        e2e = {m["name"] for m in reg.end_to_end(w["name"])}
        assert "setup_s" in e2e and len(e2e) >= 2
        layers = reg.per_layer(w["name"])
        assert layers and all(m["moves"] in e2e for m in layers)


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "service_smallflush", "--seed", "1", "--seconds", "1", "--trace",
         "0"], capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""


def test_cells_added_as_files_only(tiny_root):
    """The tiny cells of ``tiny_root`` (configuration, reference, limits,
    traffic and entries, all new files) resolve without a code change, and
    so does a new per-layer metric."""
    metric = os.path.join(tiny_root, "bench", "metrics", "flushes.tiny.py")
    with open(metric, "w") as f:
        f.write("def read(ctx):\n    return ctx.counters.get('flushes')\n")
    spec_path = os.path.join(tiny_root, "BENCHMARK.json")
    spec = json.load(open(spec_path))
    spec["per_layer"].append({
        "name": "flushes.tiny", "unit": "flushes", "better": "higher",
        "source": "program_counter", "layer": "service front end",
        "moves": "decisions_per_s", "workloads": ["tiny_service_cell"]})
    json.dump(spec, open(spec_path, "w"))
    reg = harness.Registry(tiny_root)
    for cell in ("tiny_engine_cell", "tiny_service_cell"):
        w = reg.cell(cell)
        cfg = reg.config(w["config"])
        assert reg.traffic(w["traffic"])["kind"]
        assert reg.runner(cfg["runner"]).Run
        assert reg.reference(w["config"])
        assert reg.limits(w["config"])
    names = [m["name"] for m in reg.per_layer("tiny_service_cell")]
    assert "flushes.tiny" in names
    ctx = harness.RunContext(None, None, None, None, {"flushes": 3}, None)
    assert reg.reader("flushes.tiny").read(ctx) == 3
