"""The scheduling-only cell (``"runner": "schedule"``) at a tiny size on
the CPU, the fused kernel in interpret mode: the program passes the
limits of ``vi_a_fleet_1m.limits.json``; the bfloat16 control, a carry
left unchanged and an altered answer each fail at least one; a client at
a tie of its two Theorem-2 candidates may keep either; and the kernel's
roofline reads only the one-dimensional kernel's time."""

import importlib.util
import json
import os
import shutil

import numpy as np
import pytest

import control
import harness
from conftest import BENCH, run_cell

CONFIGS = os.path.join(BENCH, "configs")

TINY_FLEET = dict(
    json.load(open(os.path.join(CONFIGS, "vi_a_fleet_1m.json"))),
    name="tiny_fleet", n_clients=384, sel_cap=48)
TINY_CHUNKS = {"kind": "schedule_chunks", "chunk_rounds": 4,
               "setup_chunks": 2, "trace_seconds": 1}
CELL = "tiny_fleet_cell"


@pytest.fixture
def fleet_root(tiny_root):
    """``tiny_root`` with a tiny fleet cell added as files and entries."""
    configs = os.path.join(tiny_root, "bench", "configs")
    with open(os.path.join(configs, "tiny_fleet.json"), "w") as f:
        json.dump(TINY_FLEET, f)
    for ext in ("_ref.py", ".limits.json"):
        shutil.copy(os.path.join(configs, "vi_a_fleet_1m" + ext),
                    os.path.join(configs, "tiny_fleet" + ext))
    with open(os.path.join(tiny_root, "bench", "traffic",
                           "tiny_chunks.json"), "w") as f:
        json.dump(TINY_CHUNKS, f)
    path = os.path.join(tiny_root, "BENCHMARK.json")
    spec = json.load(open(path))
    spec["configs"].append({
        "name": "tiny_fleet", "source": TINY_FLEET["source"],
        "file": "bench/configs/tiny_fleet.json", "reduced": [],
        "why": "tiny test size"})
    spec["workloads"].append({"name": CELL, "config": "tiny_fleet",
                              "traffic": "tiny_chunks", "chips": 1,
                              "why": "tiny test size"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "fleet_1m" in m.get("workloads", ()):
            m["workloads"].append(CELL)
    json.dump(spec, open(path, "w"))
    return tiny_root


def _ref():
    spec = importlib.util.spec_from_file_location(
        "fleet_ref", os.path.join(CONFIGS, "vi_a_fleet_1m_ref.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _limits():
    with open(os.path.join(CONFIGS, "vi_a_fleet_1m.limits.json")) as f:
        return json.load(f)


def _fails(gaps, limits):
    return any(gaps[k] > v for k, v in limits.items())


def _judge_fails(gaps, limits):
    """The limits that ``judge`` alone reads (the runner adds the run
    key's and the overflow's)."""
    return _fails(gaps, {k: v for k, v in limits.items()
                         if k not in ("key_mismatch", "overflow")})


def test_program_is_correct(fleet_root, capsys):
    rc, res = run_cell(fleet_root, CELL, capsys)
    assert rc == 0 and res["correct"], res["checks"]
    assert set(res["metrics"]) == {"rounds_per_s", "setup_s"}
    assert res["checks"]["overflow"]["value"] == 0
    assert res["checks"]["id_mismatch"]["value"] == 0


def test_window_pulls_every_chunk_once(fleet_root):
    """The window pulls the rows of each chunk it dispatched once, in
    order, before its clock stops."""
    reg = harness.Registry(fleet_root)
    run = reg.runner("schedule").Run(reg.config("tiny_fleet"),
                                     reg.traffic("tiny_chunks"), 3,
                                     reg.reference("tiny_fleet"))
    pulled = []
    take = run._take
    run._take = lambda rows: pulled.append(rows["t_comm"]) or take(rows)
    run.setup()
    run.window(0.2)
    assert len(pulled) == TINY_CHUNKS["setup_chunks"] + run.chunks
    assert run.attempted == run.chunks * TINY_CHUNKS["chunk_rounds"]
    run.release()
    assert not _fails(run.compare(), _limits())


def test_control_and_frozen_carry_fail(fleet_root):
    reg = harness.Registry(fleet_root)
    limits = reg.limits("tiny_fleet")
    cfg = reg.config("tiny_fleet")
    run = reg.runner("schedule").Run(cfg, reg.traffic("tiny_chunks"), 3,
                                     reg.reference("tiny_fleet"))
    run.setup()
    run.window(0.2)
    run.release()
    program = run.compare()
    assert not _fails(program, limits), program
    assert _fails(run.compare(control=True), limits)
    frozen = run.compare(fault="frozen")
    assert frozen["key_mismatch"] > 0 and frozen["z_gap"] > limits["z_gap"]


def test_control_readings(fleet_root):
    r = control.readings(harness.Registry(fleet_root), CELL, 4, seconds=0.2)
    limits = _limits()
    assert not _fails(r["program"], limits), r
    assert _fails(r["control"], limits), r


def _ids_shifted(monkeypatch):
    from repro.fl import client_shard
    real = client_shard.ScheduleChunks.unpack

    def unpack(self, rows):
        out = real(self, rows)
        out["ids"][:, 0] += 1
        return out
    monkeypatch.setattr(client_shard.ScheduleChunks, "unpack", unpack)


def _frozen_in_window(monkeypatch):
    """Chunks after the set-up's return their carry unchanged."""
    import jax
    import jax.numpy as jnp
    from repro.fl import client_shard
    real = client_shard.ScheduleChunks.__call__
    calls = []

    def call(self, carry, n_rounds):
        calls.append(n_rounds)
        if len(calls) <= TINY_CHUNKS["setup_chunks"]:
            return real(self, carry, n_rounds)
        _, rows = real(self, jax.tree.map(jnp.copy, carry), n_rounds)
        return carry, rows
    monkeypatch.setattr(client_shard.ScheduleChunks, "__call__", call)


@pytest.mark.parametrize("plant", [_ids_shifted, _frozen_in_window],
                         ids=["ids_shifted", "frozen_in_window"])
def test_planted_fault_is_not_correct(fleet_root, capsys, monkeypatch,
                                      plant):
    plant(monkeypatch)
    rc, res = run_cell(fleet_root, CELL, capsys)
    assert rc == 0 and res["correct"] is False, res["checks"]


def _chain(ref, key, cfg, rounds, flip=None):
    """The float64 chain from empty queues, client ``flip[1]`` keeping the
    other Theorem-2 candidate in round ``flip[0]``: -> the program's
    rows and end queues; per round and client the relative objective gap
    of the two candidates (inf where they are the same) and how far
    apart they put its queue."""
    t2, t = ref.t2, ref.vi_a.tenant(cfg)
    z = np.zeros((1, cfg["n_clients"]))
    sels, t_comm, power, gaps, apart = [], [], [], [], []
    for r in range(rounds):
        key, g, u, _ = ref.vi_a.draws(key, cfg)
        (q_i, p_i, f_i), (q_b, p_b, f_b) = t2.candidates(g, z, t)
        use_int = np.isfinite(f_i) & (f_i <= f_b)
        if flip is not None and flip[0] == r:
            use_int[0, flip[1]] = ~use_int[0, flip[1]]
        q = np.where(use_int, q_i, q_b)
        p = np.where(use_int, p_i, p_b)
        same = (q_i == q_b) & (p_i == p_b)
        gaps.append(np.where(same, np.inf,
                             np.abs(f_i - f_b) / np.abs(f_b))[0])
        apart.append(np.abs(p_i * q_i - p_b * q_b)[0])
        sel = t2.select(u, q, cfg["guarantee_one"])
        tc, pw = t2.account(sel, q, p, g, t)
        sels.append(sel[0])
        t_comm.append(float(tc[0]))
        power.append(float(pw[0]))
        z = t2.queue_update(z, q, p, t.p_bar)
    ids, n_sel, overflow = ref.rows_of(np.array(sels), cfg["sel_cap"])
    prog = dict(ids=ids, n_sel=n_sel, overflow=overflow,
                t_comm=np.array(t_comm), power=np.array(power), z=z[0])
    return prog, np.array(gaps), np.array(apart)


def test_tie_client_may_keep_either_candidate(monkeypatch):
    """A client whose two candidates lie within ``TIE`` may keep the
    other one, and its later rounds follow that branch; the same choice
    where they lie farther apart fails."""
    import jax
    ref = _ref()
    cfg, limits = TINY_FLEET, _limits()
    key = jax.random.PRNGKey(8)
    own, gaps, apart = _chain(ref, key, cfg, 6)
    assert not _judge_fails(ref.judge(key, cfg, own), limits)
    # in round 1 or 2, the two-candidate client whose candidates put its
    # queue farthest apart
    apart = np.where(np.isfinite(gaps), apart, -1.0)[1:3]
    r, lane = np.unravel_index(np.argmax(apart), apart.shape)
    r, gap = r + 1, gaps[r + 1, lane]
    prog, _, _ = _chain(ref, key, cfg, 6, flip=(r, lane))
    assert np.abs(prog["z"] - own["z"]).max() > 0
    monkeypatch.setattr(ref, "TIE", gap * 1.01)
    kept_other = ref.judge(key, cfg, prog)
    assert not _judge_fails(kept_other, limits), kept_other
    assert kept_other["tie_lanes"] >= 1
    monkeypatch.setattr(ref, "TIE", gap * 0.99)
    assert _judge_fails(ref.judge(key, cfg, prog), limits)


class _Trace:
    def __init__(self, op_s):
        self.op_s = op_s


def test_roofline_reads_the_one_dimensional_kernel_alone():
    reader = harness.Registry().reader("decision_fused_roofline")
    peaks = {"hbm_bytes_per_s": 1e9, "bf16_flops_per_s": 1e12}
    counters = {"kernel_bytes": 2e6, "kernel_ops": 1e6}
    ctx = harness.RunContext(None, None, None, peaks, dict(counters),
                             _Trace({"decision_fused.3": 0.002,
                                     "decision_fused": 0.002,
                                     "decision_fused_batched.1": 1.0,
                                     "fusion.7": 1.0}))
    assert reader.read(ctx) == pytest.approx(50.0)
    assert ctx.counters["kernel_bound"] == "memory"
    ctx = harness.RunContext(None, None, None, peaks, dict(counters),
                             _Trace({"decision_fused_batched.1": 1.0}))
    assert reader.read(ctx) is None
