"""Telemetry layer (repro.obs): registry semantics, exporters, and the
binding NEUTRALITY contract.

The contract that makes telemetry safe to thread through every hot path:
all recording is host-side, outside jit, so instrumented code paths are
BITWISE-identical with telemetry on and off. Pinned here for the three
instrumented engines the issue names — the scan engine, the composed 2D
mesh leg, and the service's flush + replay (including eviction churn).
Also pinned: the recompile counter reproduces the PR-8 warmup story
(misses on the serving path before ``warmup()``, zero after), the
replay-log growth warning fires exactly once, and the disabled-path
recorder is cheap enough to leave compiled in (loose micro-check).
"""

import glob
import json
import time
import warnings

import jax
import numpy as np
import pytest

from repro import obs
from repro.core import ChannelConfig, SchedulerConfig, heterogeneous_sigmas
from repro.core.policies import POLICY_DRAWS
from repro.data.synthetic import make_cifar10_like
from repro.fl.engine import SimConfig, run_simulation_scan
from repro.models.registry import make_model
from repro.service import SchedulerService

pytestmark = pytest.mark.obs

N = 24


@pytest.fixture(autouse=True)
def _default_off():
    """Tests may flip the process-wide switch; always restore OFF."""
    yield
    obs.configure(False)


def _configs(n=N, **kw):
    scfg = SchedulerConfig(n_clients=n, model_bits=32 * 50000.0, **kw)
    ch = ChannelConfig(n_clients=n)
    return scfg, ch


def _stream(rng, n, rounds, policy="proposed", seed0=0):
    """A deterministic (gains, raw) request stream."""
    out = []
    for t in range(rounds):
        gains = rng.uniform(0.2, 3.0, n).astype(np.float32)
        raw = POLICY_DRAWS[policy](jax.random.PRNGKey(seed0 + t), n)
        out.append((gains, raw))
    return out


# --------------------------------------------------------------------------
# Registry semantics.
# --------------------------------------------------------------------------

def test_registry_get_or_create_and_values():
    r = obs.new_registry(True)
    c = r.counter("x_total", k="a")
    assert r.counter("x_total", k="a") is c     # get-or-create identity
    assert r.counter("x_total", k="b") is not c  # labels distinguish
    c.inc()
    c.inc(2.5)
    r.counter("x_total", k="b").inc(4)
    assert r.value("x_total", k="a") == 3.5
    assert r.total("x_total") == 7.5
    g = r.gauge("depth")
    g.set(7)
    g.set(3)
    assert r.value("depth") == 3.0
    with pytest.raises(TypeError):
        r.gauge("x_total", k="a")               # kind conflict


def test_histogram_buckets_percentiles_and_ring():
    r = obs.new_registry(True)
    h = r.histogram("lat", edges=(1.0, 2.0, 4.0), ring=8)
    for v in (0.5, 1.5, 3.0, 100.0):
        h.record(v)
    assert list(h.counts) == [1, 1, 1, 1]       # last slot = overflow
    assert h.count == 4 and h.total == 105.0
    for v in range(16):                          # wrap the ring
        h.record(float(v))
    assert h.recent().shape == (8,)              # bounded
    assert 7.0 <= h.percentile(50) <= 13.0       # over the last 8 values
    with pytest.raises(ValueError):
        r.histogram("bad", edges=(2.0, 1.0))


def test_disabled_registry_hands_out_noop():
    r = obs.new_registry(False)
    assert r.counter("a") is obs.NOOP
    assert r.gauge("b") is obs.NOOP
    assert r.histogram("c") is obs.NOOP
    obs.NOOP.inc()
    obs.NOOP.set(3)
    obs.NOOP.record(0.1)                         # all no-ops
    assert r.snapshot() == []
    assert r.value("a") == 0.0


def test_configure_switch_and_inheritance():
    assert not obs.enabled()                     # process default: OFF
    reg = obs.configure(True)
    assert obs.enabled() and reg is obs.default_registry()
    assert obs.new_registry().enabled            # None inherits the switch
    assert not obs.new_registry(False).enabled   # explicit overrides
    obs.configure(False)
    assert not obs.enabled()
    assert not obs.new_registry().enabled


def test_noop_record_path_is_cheap():
    """The disabled hot path is one attribute load + empty call — assert
    LOOSELY (well under 5us/op even on a loaded CI runner) that nothing
    heavyweight snuck into the no-op recorder."""
    c = obs.new_registry(False).counter("x")
    n = 100_000
    t0 = time.perf_counter()
    for _ in range(n):
        c.inc()
    per_op = (time.perf_counter() - t0) / n
    assert per_op < 5e-6, f"no-op inc() costs {per_op * 1e9:.0f} ns/op"


def test_compile_tracker_miss_warm_forget():
    t = obs.CompileTracker(obs.new_registry(True), "x")
    assert t.miss(("b", 8)) is True
    assert t.miss(("b", 8)) is False             # seen: no new miss
    assert t.misses_total() == 1.0
    assert t.warm(("b", 16)) is True             # warmup-seeded
    assert t.miss(("b", 16)) is False
    assert t.warm_hits.value == 1.0              # hit on a warmed shape
    assert t.misses_total() == 2.0


# --------------------------------------------------------------------------
# Exporters.
# --------------------------------------------------------------------------

def test_prometheus_text_format():
    r = obs.new_registry(True)
    r.counter("req_total", bucket="b32").inc(3)
    r.gauge("depth").set(2)
    h = r.histogram("lat_seconds", edges=(1.0, 2.0))
    for v in (0.5, 1.5, 9.0):
        h.record(v)
    text = obs.prometheus_text(r)
    assert "# TYPE req_total counter" in text
    assert 'req_total{bucket="b32"} 3' in text
    assert "# TYPE depth gauge" in text and "depth 2" in text
    # histogram: cumulative buckets, +Inf == count, sum/count series
    assert 'lat_seconds_bucket{le="1"} 1' in text
    assert 'lat_seconds_bucket{le="2"} 2' in text
    assert 'lat_seconds_bucket{le="+Inf"} 3' in text
    assert "lat_seconds_sum 11" in text
    assert "lat_seconds_count 3" in text


def test_json_snapshot_is_serializable():
    r = obs.new_registry(True)
    r.counter("a").inc()
    r.histogram("b").record(0.01)
    snap = obs.json_snapshot(r, extra_field=7)
    parsed = json.loads(json.dumps(snap))
    assert parsed["extra_field"] == 7
    names = {m["name"] for m in parsed["metrics"]}
    assert names == {"a", "b"}


def test_event_log_jsonl_and_once(tmp_path):
    path = tmp_path / "events.jsonl"
    el = obs.EventLog(str(path), keep=3)
    el.emit("admit", tenant="t0")
    assert el.once("k", "warn", x=1) is not None
    assert el.once("k", "warn", x=2) is None     # suppressed repeat
    for i in range(5):
        el.emit("tick", i=i)
    assert len(el.events) == 3                   # bounded in-memory tail
    lines = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert [ln["event"] for ln in lines] == (
        ["admit", "warn"] + ["tick"] * 5)        # file keeps everything
    assert lines[1]["x"] == 1


def test_trace_span_disabled_and_enabled(tmp_path):
    """``obs.span`` is not gated on the telemetry switch: a trace holds
    it with telemetry off and on; with no profiler session it is inert."""
    with obs.span("x"):                          # no session: records nothing
        pass
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("service.flush", flush=0):     # telemetry off
            pass
        obs.configure(True)
        with obs.span("service.flush", flush=1):     # telemetry on
            pass
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    got = sorted(int(dict(e.stats)["flush"]) for p in pd.planes
                 for line in p.lines for e in line.events
                 if e.name.split("#", 1)[0] == "service.flush")
    assert got == [0, 1]


# --------------------------------------------------------------------------
# The neutrality contract: telemetry-on == telemetry-off, bitwise.
# --------------------------------------------------------------------------

def _mixed_service(telemetry, **kw):
    svc = SchedulerService(telemetry=telemetry, **kw)
    s1, c1 = _configs()
    s2, c2 = _configs(n=70)                      # second bucket
    svc.add_tenant("a", s1, c1)
    svc.add_tenant("b", s2, c2, policy="uniform", m_avg=5.0)
    return svc


def _serve(svc, streams, evict_at=2):
    """Drive both tenants, with an evict/reload cycle for 'b' midway."""
    out = []
    for t, ((ga, ra), (gb, rb)) in enumerate(streams):
        if t == evict_at:
            svc.evict("b")
            svc.reload("b")
        svc.submit("a", ga, raw=ra)
        svc.submit("b", gb, raw=rb)
        out.append(svc.flush())
    return out


def test_service_flush_replay_neutrality_bitwise(tmp_path):
    rng = np.random.default_rng(0)
    streams = list(zip(_stream(rng, N, 5),
                       _stream(np.random.default_rng(1), 70, 5,
                               policy="uniform", seed0=100)))
    svc_on = _mixed_service(True, log_warn_bytes=1.0,
                            event_log=str(tmp_path / "ev.jsonl"))
    svc_off = _mixed_service(False)
    svc_on.warmup(max_batch=2)
    svc_off.warmup(max_batch=2)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        got_on = _serve(svc_on, streams)
        got_off = _serve(svc_off, streams)
    for r_on, r_off in zip(got_on, got_off):
        for name in ("a", "b"):
            for f_on, f_off in zip(r_on[name], r_off[name]):
                np.testing.assert_array_equal(f_on, f_off)
    # live queue state bitwise too
    for name in ("a", "b"):
        for l_on, l_off in zip(svc_on.tenant_state(name),
                               svc_off.tenant_state(name)):
            np.testing.assert_array_equal(l_on, l_off)
    # replaying the telemetry-on log through a FRESH telemetry-on service
    # reproduces the recorded decisions bit for bit
    replayed = svc_on.log.replay(_mixed_service(True))
    assert len(replayed) > 0
    flat = {}
    for entry in replayed:
        flat.update(entry)
    for name in ("a", "b"):
        for f_rep, f_live in zip(flat[name], got_on[-1][name]):
            np.testing.assert_array_equal(f_rep, f_live)


def test_scan_engine_neutrality_bitwise():
    key = jax.random.PRNGKey(0)
    n = 12
    ds = make_cifar10_like(key, n_clients=n, per_client=16, n_test=32,
                           h=8, w=8)
    scfg = SchedulerConfig(n_clients=n, model_bits=1e5)
    ch = ChannelConfig(n_clients=n)
    sim = SimConfig(rounds=4, eval_every=2, m_cap=4, batch=4,
                    local_steps=2, eval_size=32, model="mlp")
    params = make_model("mlp", ds).init_fn(jax.random.PRNGKey(1))
    sig = heterogeneous_sigmas(n)
    h_off = run_simulation_scan(jax.random.PRNGKey(2), params, ds, sim,
                                scfg, ch, sig)
    obs.configure(True)
    h_on = run_simulation_scan(jax.random.PRNGKey(2), params, ds, sim,
                               scfg, ch, sig)
    for k in h_off:
        np.testing.assert_array_equal(h_off[k], h_on[k], err_msg=k)
    reg = obs.default_registry()
    assert reg.value("engine_runs_total") == 1.0
    assert reg.value("engine_rounds_total") == sim.rounds
    assert reg.value("engine_rounds_per_sec") > 0.0


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs >= 4 devices")
def test_mesh2d_leg_neutrality_bitwise():
    key = jax.random.PRNGKey(0)
    n = 16
    ds = make_cifar10_like(key, n_clients=n, per_client=16, n_test=32,
                           h=8, w=8)
    scfg = SchedulerConfig(n_clients=n, model_bits=1e5)
    ch = ChannelConfig(n_clients=n)
    sim = SimConfig(rounds=3, eval_every=2, m_cap=4, batch=4,
                    local_steps=2, eval_size=32, model="mlp",
                    client_shards=2, participant_shards=2)
    params = make_model("mlp", ds).init_fn(jax.random.PRNGKey(1))
    sig = heterogeneous_sigmas(n)
    h_off = run_simulation_scan(jax.random.PRNGKey(2), params, ds, sim,
                                scfg, ch, sig)
    obs.configure(True)
    h_on = run_simulation_scan(jax.random.PRNGKey(2), params, ds, sim,
                               scfg, ch, sig)
    for k in h_off:
        np.testing.assert_array_equal(h_off[k], h_on[k], err_msg=k)


# --------------------------------------------------------------------------
# Recompile tracking: the PR-8 warmup story, as counters.
# --------------------------------------------------------------------------

def test_recompile_counter_reproduces_warmup_story():
    rng = np.random.default_rng(0)
    streams = _stream(rng, N, 3)

    def serve_batches(svc):
        """Flushes of 1, then 2, then 1 requests: batch shapes 1 and 2."""
        scfg, ch = _configs()
        svc.add_tenant("a", scfg, ch)
        svc.add_tenant("b", scfg, ch)
        base = svc.obs.compiles.misses_total()
        for t, (gains, raw) in enumerate(streams):
            svc.submit("a", gains, raw=raw)
            if t == 1:
                svc.submit("b", gains, raw=raw)
            svc.flush()
        return svc.obs.compiles.misses_total() - base

    cold = serve_batches(SchedulerService(telemetry=True))
    assert cold > 0                              # serving paid compiles

    svc = SchedulerService(telemetry=True)
    scfg, ch = _configs()
    svc.add_tenant("a", scfg, ch)
    svc.add_tenant("b", scfg, ch)
    svc.warmup(max_batch=2)                      # pre-compile shapes 1, 2
    base = svc.obs.compiles.misses_total()
    for t, (gains, raw) in enumerate(streams):
        svc.submit("a", gains, raw=raw)
        if t == 1:
            svc.submit("b", gains, raw=raw)
        svc.flush()
    assert svc.obs.compiles.misses_total() - base == 0   # all warm
    assert svc.obs.compiles.warm_hits.value > 0
    assert svc.obs.registry.total("service_compile_seconds_total") > 0


def test_admitting_a_tenant_invalidates_warm_shapes():
    """Admission changes the bucket's T operand shape — a fresh compile
    the tracker must count (the exact silent-recompile pathology)."""
    svc = SchedulerService(telemetry=True)
    scfg, ch = _configs()
    svc.add_tenant("a", scfg, ch)
    svc.warmup(max_batch=1)
    base = svc.obs.compiles.misses_total()
    svc.add_tenant("c", scfg, ch)                # same bucket, new T
    gains = np.full(N, 1.0, np.float32)
    svc.submit("a", gains, key=jax.random.PRNGKey(0))
    svc.flush()
    assert svc.obs.compiles.misses_total() - base == 1.0


# --------------------------------------------------------------------------
# Replay-log growth safety + snapshot API.
# --------------------------------------------------------------------------

def test_log_growth_warning_fires_once_and_compact_resets():
    svc = _mixed_service(True, log_warn_bytes=64.0)
    rng = np.random.default_rng(0)
    ga = _stream(rng, N, 3)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        for gains, raw in ga:
            svc.submit("a", gains, raw=raw)
            svc.flush()
    growth = [w for w in caught
              if "compact_log" in str(w.message)]
    assert len(growth) == 1                      # once, not per flush
    events = [e["event"] for e in svc.events.events]
    assert events.count("log_growth_warning") == 1
    reg = svc.obs.registry
    assert reg.value("service_log_entries") == 3.0
    assert reg.value("service_log_bytes_est") > 64.0
    assert svc.log.bytes_est > 0
    svc.compact_log()
    assert svc.log.bytes_est == 0
    assert reg.value("service_log_entries") == 0.0
    assert reg.value("service_log_compactions_total") == 1.0
    assert "compact" in [e["event"] for e in svc.events.events]


def test_metrics_snapshot_formats():
    svc = _mixed_service(True)
    gains = np.full(N, 1.0, np.float32)
    svc.submit("a", gains, key=jax.random.PRNGKey(0))
    svc.flush()
    snap = svc.metrics_snapshot()
    assert snap["tenants"] == {"resident": 2, "spilled": 0}
    assert snap["log"]["entries"] == 1
    names = {m["name"] for m in snap["metrics"]}
    assert {"service_flush_seconds", "service_z_mean",
            "service_submits_total"} <= names
    parsed = json.loads(svc.metrics_snapshot(fmt="json"))
    assert parsed["queued"] == 0
    prom = svc.metrics_snapshot(fmt="prometheus")
    assert "# TYPE service_flush_seconds histogram" in prom
    assert 'service_z_mean{bucket="' in prom
    with pytest.raises(ValueError):
        svc.metrics_snapshot(fmt="xml")
    # disabled service: empty registry, and NO device pulls happen
    svc_off = _mixed_service(False)
    assert svc_off.metrics_snapshot()["metrics"] == []


def test_lifecycle_counters_and_events(tmp_path):
    svc = _mixed_service(True, spill_dir=str(tmp_path))
    reg = svc.obs.registry
    assert reg.value("service_resident_tenants") == 2.0
    assert reg.value("service_tenant_admits_total") == 2.0
    svc.evict("b")
    assert reg.value("service_resident_tenants") == 1.0
    assert reg.value("service_tenant_spills_total") == 1.0
    assert reg.value("service_spilled_tenants") == 1.0
    svc.reload("b")
    assert reg.value("service_tenant_reloads_total") == 1.0
    assert reg.value("service_spilled_tenants") == 0.0
    ev = [e["event"] for e in svc.events.events]
    assert ev == ["admit", "admit", "evict", "reload"]
    assert svc.events.events[2]["spill"] == "disk"


# --------------------------------------------------------------------------
# compare.py: per-metric threshold specs (the <5% obs_overhead gate).
# --------------------------------------------------------------------------

def test_compare_gate_per_metric_threshold(tmp_path):
    from benchmarks import compare

    assert compare.spec_of("lower") == ("lower", None)
    assert compare.spec_of({"direction": "lower", "threshold": 0.05}) \
        == ("lower", 0.05)
    spec = compare.METRICS["service"]["scenarios.obs_overhead.p50_ratio"]
    assert compare.spec_of(spec) == ("lower", 0.05)

    out_dir, base_dir = tmp_path / "out", tmp_path / "base"
    out_dir.mkdir()
    base_dir.mkdir()
    metrics = {"bench": {"a.ratio": {"direction": "lower",
                                     "threshold": 0.05},
                         "a.lat": "lower"}}
    (base_dir / "bench.json").write_text(json.dumps(
        {"a.ratio": {"value": 1.0, "direction": "lower",
                     "threshold": 0.05},
         "a.lat": {"value": 10.0, "direction": "lower"}}))

    def run(ratio, lat):
        (out_dir / "bench.json").write_text(
            json.dumps({"a": {"ratio": ratio, "lat": lat}}))
        old = compare.METRICS
        compare.METRICS = metrics
        try:
            return compare.gate(str(out_dir), str(base_dir), 0.25)
        finally:
            compare.METRICS = old

    assert run(1.04, 11.0) == 0      # ratio within 5%, lat within 25%
    assert run(1.06, 11.0) == 1      # ratio beyond its OWN 5% gate
    assert run(1.01, 13.0) == 1      # lat beyond the default 25%
