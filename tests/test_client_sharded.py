"""Client-sharded scheduling path: per-mesh parity contract + guards.

The contract (mirroring the grid's and the participant-sharded round's):

* mesh size 1 — the client-sharded engine is BITWISE-identical to
  ``run_simulation_scan``: the PRNG draws happen full-shape outside the
  shard_map (same traced draw as the sequential step), every elementwise
  stage is the same fenced code, and selections/packs/merges are value
  selections, not arithmetic.
* any mesh — the accounting island keeps n_selected exactly equal for
  the suite's fixed seeds (selections, not arithmetic — though a draw
  landing inside the ~1 ulp cross-mesh q drift could in principle flip
  one, see fl/sharding.py) and comm_time / avg_power equal to ~1 ulp:
  the reductions always associate as the fixed ACCOUNT_BLOCKS blocks
  (repro/fl/sharding.py), so every mesh adds the same partials in the
  same order; the residual is per-lane emission drift of the
  operand-driven solve (LLVM inlines/contracts per kernel shape), the
  price of the scheduler service's bitwise contract
  (repro/core/scheduler.py).
* across meshes — trained metrics (test_acc) may drift by reduction
  re-association in the surrounding program (~1 ulp/round, amplified
  through training), bounded here by the same tolerance the
  participant-sharded suite uses.

Run under scripts/test.sh the suite sees 8 virtual CPU devices; under bare
pytest there is 1 — the multi-device legs key off len(jax.devices()).

The ``massive`` marker leg re-checks the scheduling-only runner's
accounting contract at N = 10^5 (nightly CI only; see
.github/workflows/ci.yml).
"""

import dataclasses

import jax
import numpy as np
import pytest

from repro.core import ChannelConfig, SchedulerConfig, heterogeneous_sigmas
from repro.data.synthetic import make_cifar10_like
from repro.fl.client_shard import make_schedule_runner
from repro.fl.engine import (SimConfig, make_config_runner,
                             history_from_trajectory, run_simulation_scan)
from repro.fl.grid import GridSpec, run_grid
from repro.fl.simulation import run_simulation
from repro.models.registry import make_model

N = 48
HIST_KEYS = ("round", "comm_time", "test_acc", "avg_power", "n_selected")
EXACT_ACCOUNT_KEYS = ("round", "n_selected")
FLOAT_ACCOUNT_KEYS = ("comm_time", "avg_power")


def _assert_accounting(seq, shd, n_dev):
    """Cross-mesh accounting: integers exact, floats to ~1 ulp (same
    blocked association; emission-level drift only)."""
    for k in EXACT_ACCOUNT_KEYS:
        np.testing.assert_array_equal(seq[k], shd[k],
                                      err_msg=f"mesh{n_dev} {k}")
    for k in FLOAT_ACCOUNT_KEYS:
        np.testing.assert_allclose(seq[k], shd[k], rtol=3e-7, atol=0,
                                   err_msg=f"mesh{n_dev} {k}")


@pytest.fixture(scope="module")
def setup():
    key = jax.random.PRNGKey(0)
    ds = make_cifar10_like(key, n_clients=N, per_client=32, n_test=128,
                           h=8, w=8)
    ch = ChannelConfig(n_clients=N)
    scfg = SchedulerConfig(n_clients=N, model_bits=32 * 50000.0)
    return ds, ch, scfg


def _sim(**kw):
    base = dict(rounds=6, eval_every=3, m_cap=5, batch=4, local_steps=2,
                eval_size=128, model="mlp")
    base.update(kw)
    return SimConfig(**base)


def _run_three(ds, scfg, ch, sig, sim, params):
    key = jax.random.PRNGKey(2)
    seq = run_simulation_scan(key, params, ds, sim, scfg, ch, sig)
    sh1 = run_simulation_scan(key, params, ds,
                              dataclasses.replace(sim, client_shards=1),
                              scfg, ch, sig)
    n_dev = len(jax.devices())
    shd = run_simulation_scan(key, params, ds,
                              dataclasses.replace(sim,
                                                  client_shards=n_dev),
                              scfg, ch, sig)
    return seq, sh1, shd, n_dev


# >= 2 channel models x >= 2 policies, per the acceptance contract; the
# lognormal/rician rows also cover the multi-leaf and (2, N) raw shapes.
CASES = [
    ("proposed", 0.0, "rayleigh", ()),
    ("proposed", 0.0, "lognormal", (("shadow_db", 3.0),)),
    ("uniform", 4.0, "rayleigh", ()),
    ("uniform", 4.0, "gauss_markov", (("rho", 0.8),)),
    ("greedy_channel", 3.0, "rician", (("k_factor", 3.0),)),
]


@pytest.mark.parametrize("policy,uniform_m,channel,channel_params", CASES)
def test_mesh1_bitwise_and_meshN_accounting(setup, policy, uniform_m,
                                            channel, channel_params):
    ds, ch, scfg = setup
    sig = heterogeneous_sigmas(N)
    params = make_model("mlp", ds).init_fn(jax.random.PRNGKey(1))
    sim = _sim(policy=policy, uniform_m=uniform_m, channel=channel,
               channel_params=channel_params)
    seq, sh1, shd, n_dev = _run_three(ds, scfg, ch, sig, sim, params)
    for k in HIST_KEYS:
        np.testing.assert_array_equal(seq[k], sh1[k], err_msg=f"mesh1 {k}")
    _assert_accounting(seq, shd, n_dev)
    np.testing.assert_allclose(seq["test_acc"], shd["test_acc"], atol=2e-2,
                               err_msg=f"mesh{n_dev} test_acc")


def test_odd_n_pads_with_dead_lanes(setup):
    """N not a multiple of ACCOUNT_BLOCKS: pad lanes must never select,
    never contribute to accounting, and never leak NaN/inf."""
    _, _, _ = setup
    n = 21
    ds = make_cifar10_like(jax.random.PRNGKey(3), n_clients=n,
                           per_client=32, n_test=128, h=8, w=8)
    ch = ChannelConfig(n_clients=n)
    scfg = SchedulerConfig(n_clients=n, model_bits=32 * 50000.0)
    sig = heterogeneous_sigmas(n)
    params = make_model("mlp", ds).init_fn(jax.random.PRNGKey(1))
    sim = _sim(policy="proposed")
    seq, sh1, shd, n_dev = _run_three(ds, scfg, ch, sig, sim, params)
    for k in HIST_KEYS:
        np.testing.assert_array_equal(seq[k], sh1[k], err_msg=f"mesh1 {k}")
    _assert_accounting(seq, shd, n_dev)
    assert np.all(np.isfinite(shd["comm_time"]))
    assert np.all(shd["n_selected"] <= n)


def test_fused_decision_on_the_sharded_path(setup):
    """solver="pallas_fused" (interpret off-TPU) runs the fused kernel on
    each shard's client slice (``_sharded_proposed_fused``): the whole
    engine trajectory on the full mesh is bitwise-equal to the stitched
    per-shard solve on the same mesh."""
    ds, ch, scfg = setup
    sig = heterogeneous_sigmas(N)
    params = make_model("mlp", ds).init_fn(jax.random.PRNGKey(1))
    sim = _sim(rounds=4, policy="proposed",
               client_shards=len(jax.devices()))
    key = jax.random.PRNGKey(4)
    hists = {}
    for solver in ("jnp", "pallas_fused"):
        run = make_config_runner(ds, dataclasses.replace(sim, solver=solver),
                                 scfg, ch, sig)
        hists[solver] = history_from_trajectory(sim.rounds, sim.eval_every,
                                                N, *run(params, key))
    for k in HIST_KEYS:
        np.testing.assert_array_equal(hists["jnp"][k],
                                      hists["pallas_fused"][k], err_msg=k)


def test_schedule_runner_sequential_vs_sharded_exact(setup):
    """The scheduling-only massive-N driver: sequential (client_shards=0)
    and full-mesh trajectories share draws and the blocked reduce —
    n_selected exact, float accounting to ~1 ulp on any mesh."""
    n = 2400
    ch = ChannelConfig(n_clients=n)
    scfg = SchedulerConfig(n_clients=n, model_bits=32 * 555178.0)
    sig = heterogeneous_sigmas(n)
    n_dev = len(jax.devices())
    key = jax.random.PRNGKey(5)
    for policy, m_avg in (("proposed", 0.0), ("uniform", 32.0)):
        seq = make_schedule_runner(sig, scfg, ch, rounds=8, policy=policy,
                                   m_avg=m_avg, client_shards=0)(key)
        shd = make_schedule_runner(sig, scfg, ch, rounds=8, policy=policy,
                                   m_avg=m_avg,
                                   client_shards=n_dev)(key)
        np.testing.assert_array_equal(np.asarray(seq[2]),
                                      np.asarray(shd[2]),
                                      err_msg=f"{policy}/n_sel")
        for name, a, b in zip(("t_comm", "power"), seq, shd):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=3e-7, atol=0,
                                       err_msg=f"{policy}/{name}")


@pytest.mark.massive
def test_schedule_runner_parity_massive(setup):
    """The N = 10^5 leg of the same exactness contract (nightly CI)."""
    n = 100_000
    ch = ChannelConfig(n_clients=n)
    scfg = SchedulerConfig(n_clients=n, model_bits=32 * 555178.0)
    sig = heterogeneous_sigmas(n)
    n_dev = len(jax.devices())
    key = jax.random.PRNGKey(6)
    seq = make_schedule_runner(sig, scfg, ch, rounds=6, policy="proposed",
                               client_shards=0)(key)
    shd = make_schedule_runner(sig, scfg, ch, rounds=6, policy="proposed",
                               client_shards=n_dev)(key)
    np.testing.assert_array_equal(np.asarray(seq[2]), np.asarray(shd[2]),
                                  err_msg="n_sel")
    for name, a, b in zip(("t_comm", "power"), seq, shd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-7, atol=0, err_msg=name)
    assert np.all(np.asarray(seq[2]) >= 1)


def test_guards(setup):
    """Misconfigurations fail fast, not deep inside a compiled scan."""
    ds, ch, scfg = setup
    sig = heterogeneous_sigmas(N)
    params = make_model("mlp", ds).init_fn(jax.random.PRNGKey(1))
    key = jax.random.PRNGKey(2)
    # the composed 2D mesh must fit the device count
    with pytest.raises(ValueError, match="mesh"):
        run_simulation_scan(key, params, ds,
                            _sim(client_shards=len(jax.devices()),
                                 participant_shards=2),
                            scfg, ch, sig)
    # the grid owns the config axis
    with pytest.raises(ValueError, match="CONFIG axis"):
        run_grid(key, params, ds, _sim(client_shards=1), scfg, ch,
                 GridSpec())
    # the legacy loop is the sequential reference
    with pytest.raises(ValueError, match="loop engine"):
        run_simulation(key, params, ds,
                       _sim(client_shards=1, engine="loop"), scfg, ch, sig)
    # more shards than devices
    with pytest.raises(ValueError, match="client_shards"):
        run_simulation_scan(key, params, ds,
                            _sim(client_shards=len(jax.devices()) + 1),
                            scfg, ch, sig)
    # shard count must divide the fixed accounting block count
    if len(jax.devices()) >= 5:
        with pytest.raises(ValueError, match="ACCOUNT_BLOCKS"):
            run_simulation_scan(key, params, ds, _sim(client_shards=5),
                                scfg, ch, sig)
    # policies without an exact sharded form are rejected up front
    with pytest.raises(ValueError, match="sharded"):
        run_simulation_scan(key, params, ds,
                            _sim(client_shards=1, policy="update_aware",
                                 uniform_m=4.0), scfg, ch, sig)
    # baselines still need a matched M (mirrors make_policy's check)
    with pytest.raises(ValueError, match="m_avg"):
        make_schedule_runner(sig, scfg, ch, rounds=2, policy="uniform",
                             m_avg=0.0, client_shards=1)
