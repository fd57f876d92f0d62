"""Program spans and named scopes, read back from CPU profiler traces.

The service's flush and the engine's chunk call record ``repro.obs.span``
host spans whether telemetry is on or off; the engine round's device
operations carry the named scopes ``fl.decision``, ``fl.update`` and
``fl.eval`` in their HLO ``op_name`` metadata. A benchmark reduces both
from a device trace, so their names, nesting and counts are pinned here.
"""

import contextlib
import glob
import os
import re
from typing import NamedTuple

import jax
import numpy as np
import pytest

from repro.core import ChannelConfig, SchedulerConfig, heterogeneous_sigmas
from repro.core.policies import POLICY_DRAWS
from repro.data.synthetic import make_cifar10_like
from repro.fl.decision import decision_coeffs
from repro.fl.engine import (SimConfig, init_carry, make_chunk_runner,
                             make_eval_fn, make_sim_round, scan_chunk)
from repro.models.registry import make_model
from repro.service import SchedulerService

PREFIXES = ("service.", "fl.")
PULLS_PER_GROUP = 6       # sel, q, p, t_comm, power, n_sel


class Span(NamedTuple):
    name: str
    start: float
    end: float
    flush: int            # -1 where the span carries no flush ordinal


def _program_spans(trace_dir):
    """The program's spans of the newest trace under ``trace_dir``, in
    order of start (an enclosing span before the spans it holds)."""
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    pd = jax.profiler.ProfileData.from_file(max(files, key=os.path.getmtime))
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for e in line.events:
                name = e.name.split("#", 1)[0]
                if name.startswith(PREFIXES):
                    stats = dict(e.stats)
                    out.append(Span(name, e.start_ns,
                                    e.start_ns + e.duration_ns,
                                    int(stats.get("flush", -1))))
    return sorted(out, key=lambda s: (s.start, -s.end))


@contextlib.contextmanager
def _traced(trace_dir):
    jax.profiler.start_trace(str(trace_dir))
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _two_bucket_service():
    """Two tenants in two buckets, so one flush serves two groups."""
    svc = SchedulerService(telemetry=False)
    for name, n, policy in (("a", 24, "proposed"), ("b", 70, "uniform")):
        svc.add_tenant(name, SchedulerConfig(n_clients=n, model_bits=1e6),
                       ChannelConfig(n_clients=n), policy=policy,
                       m_avg=5.0)
    return svc


def _submit_all(svc, rng, t):
    for name in ("a", "b"):
        n = svc.store.spec(name).n
        policy = svc.store.spec(name).policy
        svc.submit(name, rng.uniform(0.2, 3.0, n).astype(np.float32),
                   raw=POLICY_DRAWS[policy](jax.random.PRNGKey(t), n))


def test_service_flush_spans_nest_and_count_transfers(tmp_path):
    rng = np.random.default_rng(0)
    svc = _two_bucket_service()
    _submit_all(svc, rng, 0)
    svc.flush()                                  # flush 0 compiles
    with _traced(tmp_path):
        _submit_all(svc, rng, 1)
        out = svc.flush()                        # flush 1, traced
    assert set(out) == {"a", "b"}
    spans = _program_spans(tmp_path)
    assert {s.flush for s in spans} == {1}
    submits = [s for s in spans if s.name == "service.submit"]
    roots = [s for s in spans if s.name == "service.flush"]
    assert len(submits) == 2 and len(roots) == 1
    root = roots[0]
    assert all(s.end <= root.start for s in submits)
    inner = [s for s in spans if s not in submits and s is not root]
    assert all(root.start <= s.start and s.end <= root.end for s in inner)
    # per group: stage, dispatch and the log append, both groups in
    # flight before any pull; then per group its six transfers, each in
    # a span of its own, and the building of its decisions
    group_out = ["service.pull"] * PULLS_PER_GROUP + ["service.unpack"]
    assert [s.name for s in inner] == (
        ["service.stage", "service.dispatch", "service.log"] * 2
        + group_out * 2)


def test_service_spans_without_replay_log(tmp_path):
    rng = np.random.default_rng(1)
    svc = _two_bucket_service()
    _submit_all(svc, rng, 0)
    svc.flush(log=False)
    with _traced(tmp_path):
        _submit_all(svc, rng, 1)
        svc.flush(log=False)
    names = [s.name for s in _program_spans(tmp_path)]
    assert "service.log" not in names
    assert names.count("service.pull") == 2 * PULLS_PER_GROUP


def _tiny_engine():
    n = 12
    ds = make_cifar10_like(jax.random.PRNGKey(0), n_clients=n,
                           per_client=16, n_test=32, h=8, w=8)
    scfg = SchedulerConfig(n_clients=n, model_bits=1e5)
    ch = ChannelConfig(n_clients=n)
    sim = SimConfig(rounds=2, eval_every=1, m_cap=4, batch=4,
                    local_steps=2, eval_size=32, model="mlp")
    params = make_model("mlp", ds).init_fn(jax.random.PRNGKey(1))
    return ds, sim, scfg, ch, heterogeneous_sigmas(n), params


def test_engine_chunk_call_is_one_dispatch_span(tmp_path):
    ds, sim, scfg, ch, sig, params = _tiny_engine()
    run_chunk = make_chunk_runner(ds, sim, scfg, ch, sig)
    carry = init_carry(jax.random.PRNGKey(2), params, scfg, sim, sig, ch)
    carry, acc, _ = run_chunk(carry, 1)          # compiles
    jax.block_until_ready(acc)
    with _traced(tmp_path):
        for _ in range(3):
            carry, acc, _ = run_chunk(carry, 1)
            jax.block_until_ready(acc)
    assert [s.name for s in _program_spans(tmp_path)] == ["fl.dispatch"] * 3


@pytest.fixture(scope="module")
def chunk_hlo():
    """The compiled text of one scan-engine chunk (as the chunk runner
    builds it)."""
    ds, sim, scfg, ch, sig, params = _tiny_engine()

    def chunk(carry, co, data):
        sim_round = make_sim_round(data, sim, scfg, ch, sig, coeffs=co)
        return scan_chunk(sim_round, make_eval_fn(data, sim), carry, 1)

    carry = init_carry(jax.random.PRNGKey(2), params, scfg, sim, sig, ch)
    return jax.jit(chunk).lower(carry, decision_coeffs(scfg, ch),
                                ds).compile().as_text()


@pytest.mark.parametrize("scope", ["fl.decision", "fl.update", "fl.eval"])
def test_round_scopes_in_op_name_metadata(chunk_hlo, scope):
    """The compiled chunk's operations name their part of the round in
    ``op_name``, the metadata a device trace carries per operation."""
    assert re.search(rf'op_name="([^"]*/)?{re.escape(scope)}/', chunk_hlo)
