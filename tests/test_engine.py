"""Scan-compiled engine: parity with the legacy loop, the solver switch,
and the policy x seed sweep (repro/fl/engine.py)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ChannelConfig, SchedulerConfig, heterogeneous_sigmas
from repro.data.synthetic import make_cifar10_like, make_lm_federated
from repro.fl.engine import (SimConfig, eval_rounds, history_from_trajectory,
                             run_simulation_scan, run_sweep)
from repro.fl.round import pack_participants
from repro.fl.simulation import run_simulation, run_simulation_loop
from repro.models.cnn import CNNConfig, init_cnn
from repro.models.registry import make_model

N = 40
HIST_KEYS = ("round", "comm_time", "test_acc", "avg_power", "n_selected")


@pytest.fixture(scope="module")
def small_setup():
    key = jax.random.PRNGKey(0)
    ds = make_cifar10_like(key, n_clients=N, per_client=64, n_test=400,
                           h=16, w=16)
    cnn = CNNConfig(16, 16, 3, 10, conv1=8, conv2=16, hidden=32)
    params = init_cnn(jax.random.PRNGKey(1), cnn)
    ch = ChannelConfig(n_clients=N)
    scfg = SchedulerConfig(n_clients=N, model_bits=32 * 50000.0, lam=10.0,
                           V=1000.0)
    return ds, params, ch, scfg


def _sim(policy="proposed", **kw):
    base = dict(rounds=13, eval_every=5, m_cap=6, batch=8, local_steps=3,
                eval_size=400, policy=policy)
    base.update(kw)
    return SimConfig(**base)


@pytest.mark.parametrize("policy,uniform_m", [("proposed", 0.0),
                                              ("uniform", 5.0)])
def test_scan_matches_loop_history(small_setup, policy, uniform_m):
    """Same PRNG key -> same trajectory from two independent engines."""
    ds, params, ch, scfg = small_setup
    sig = heterogeneous_sigmas(N)
    sim = _sim(policy, uniform_m=uniform_m)
    h_loop = run_simulation_loop(jax.random.PRNGKey(2), params, ds, sim,
                                 scfg, ch, sig)
    h_scan = run_simulation_scan(jax.random.PRNGKey(2), params, ds, sim,
                                 scfg, ch, sig)
    assert set(h_loop) == set(h_scan) == set(HIST_KEYS)
    np.testing.assert_array_equal(h_loop["round"], h_scan["round"])
    np.testing.assert_array_equal(h_loop["n_selected"], h_scan["n_selected"])
    for k in ("comm_time", "test_acc", "avg_power"):
        # float32 accumulation order differs between the engines
        np.testing.assert_allclose(h_loop[k], h_scan[k], rtol=5e-4,
                                   atol=1e-5, err_msg=k)


@pytest.mark.parametrize("model,aggregation,wire", [
    ("cnn", "delta", "float32"),
    ("cnn", "delta", "bfloat16"),
    ("mlp", "paper", "float32"),
    ("mlp", "delta", "bfloat16"),
    ("transformer_lm", "paper", "float32"),
    ("transformer_lm", "delta", "float32"),
])
def test_scan_matches_loop_all_models_and_delta(small_setup, model,
                                                aggregation, wire):
    """The two independently-implemented engines agree for EVERY registered
    model and for the variance-reduced delta aggregation (incl. its bf16
    wire) — the legacy loop used to hard-code the CNN + paper aggregation,
    leaving this whole surface untested."""
    ds_img, _, ch, scfg = small_setup
    if model == "transformer_lm":
        ds = make_lm_federated(jax.random.PRNGKey(0), n_clients=N,
                               per_client=32, seq=12, vocab=16, n_test=256)
    else:
        ds = ds_img
    mp = (("conv1", 8), ("conv2", 16), ("hidden", 32)) if model == "cnn" \
        else ()
    sim = _sim(rounds=6, eval_every=3, local_steps=2, model=model,
               model_params=mp, aggregation=aggregation, wire_dtype=wire)
    params = make_model(model, ds, **dict(mp)).init_fn(jax.random.PRNGKey(1))
    h_loop = run_simulation_loop(jax.random.PRNGKey(2), params, ds, sim,
                                 scfg, ch, sig := heterogeneous_sigmas(N))
    h_scan = run_simulation_scan(jax.random.PRNGKey(2), params, ds, sim,
                                 scfg, ch, sig)
    np.testing.assert_array_equal(h_loop["round"], h_scan["round"])
    np.testing.assert_array_equal(h_loop["n_selected"], h_scan["n_selected"])
    for k in ("comm_time", "test_acc", "avg_power"):
        np.testing.assert_allclose(h_loop[k], h_scan[k], rtol=5e-4,
                                   atol=1e-5, err_msg=f"{model}/{k}")


@pytest.mark.parametrize("rounds,eval_every", [
    (4, 10),    # eval_every > rounds: round 0 + final round only
    (13, 5),    # eval stride does not divide rounds: tail chunk
    (1, 3),     # single round: the round-0 eval IS the final eval
    (7, 7),     # stride == rounds: no full chunk, tail of rounds-1
    (10, 5),    # final round lands exactly on the stride: no tail chunk
])
def test_eval_bookkeeping_awkward_shapes(small_setup, rounds, eval_every):
    """eval_rounds / the chunk schedule / the legacy loop must agree on
    WHICH rounds get recorded for every awkward (rounds, eval_every)
    combination — the chunk math ((rounds-1)//eval_every full chunks plus
    a tail) silently disagreeing with the loop's modulo rule would skew
    every downstream trajectory comparison."""
    ds, params, ch, scfg = small_setup
    sig = heterogeneous_sigmas(N)
    sim = _sim(rounds=rounds, eval_every=eval_every, local_steps=1, m_cap=3)
    ev = eval_rounds(rounds, eval_every)
    assert ev[0] == 0 and ev[-1] == rounds - 1
    assert len(set(ev)) == len(ev)
    h_loop = run_simulation_loop(jax.random.PRNGKey(11), params, ds, sim,
                                 scfg, ch, sig)
    h_scan = run_simulation_scan(jax.random.PRNGKey(11), params, ds, sim,
                                 scfg, ch, sig)
    assert h_loop["round"].tolist() == ev == h_scan["round"].tolist()
    np.testing.assert_array_equal(h_loop["n_selected"],
                                  h_scan["n_selected"])
    for k in ("comm_time", "test_acc", "avg_power"):
        np.testing.assert_allclose(h_loop[k], h_scan[k], rtol=5e-4,
                                   atol=1e-5, err_msg=k)
        assert h_scan[k].shape == (len(ev),)


def test_history_from_trajectory_layout():
    """The device-array -> history conversion keeps the eval-point axis
    aligned with eval_rounds and reproduces the loop engine's host-side
    float64 avg_power math."""
    rounds, eval_every, n_clients = 7, 3, 10
    ev = eval_rounds(rounds, eval_every)
    e = len(ev)
    comm = jnp.arange(1.0, e + 1)
    acc = jnp.linspace(0.1, 0.9, e)
    pcum = jnp.arange(10.0, 10.0 + e)
    nsel = jnp.arange(1, e + 1)
    h = history_from_trajectory(rounds, eval_every, n_clients, comm, acc,
                                pcum, nsel)
    assert h["round"].tolist() == ev
    assert h["avg_power"].dtype == np.float64
    np.testing.assert_allclose(
        h["avg_power"],
        np.arange(10.0, 10.0 + e) / (np.asarray(ev) + 1) / n_clients)
    assert h["n_selected"].dtype == np.int64


def test_run_simulation_dispatches_on_engine(small_setup):
    ds, params, ch, scfg = small_setup
    sig = heterogeneous_sigmas(N)
    sim = _sim(rounds=4, eval_every=3, local_steps=1)
    h_default = run_simulation(jax.random.PRNGKey(3), params, ds, sim, scfg,
                               ch, sig)
    h_scan = run_simulation_scan(jax.random.PRNGKey(3), params, ds, sim,
                                 scfg, ch, sig)
    for k in HIST_KEYS:
        np.testing.assert_allclose(h_default[k], h_scan[k], rtol=1e-6)
    with pytest.raises(ValueError):
        run_simulation(jax.random.PRNGKey(3), params, ds,
                       dataclasses.replace(sim, engine="bogus"), scfg, ch,
                       sig)


def _solver_entry_points():
    """Each public entry point that takes a ``solver``, as ``(name,
    call(setup, solver))`` — the call builds (and where building is lazy,
    runs) the entry point at a tiny size."""
    from repro.fl.client_shard import make_schedule_chunk_runner
    from repro.fl.engine import init_carry, make_chunk_runner
    from repro.fl.grid import GridSpec, run_grid
    from repro.fl.population import make_population_round
    from repro.service import SchedulerService

    def chunk_runner(setup, solver):
        ds, params, ch, scfg = setup
        sig = heterogeneous_sigmas(N)
        sim = _sim(solver=solver)
        run = make_chunk_runner(ds, sim, scfg, ch, sig)
        run(init_carry(jax.random.PRNGKey(0), params, scfg, sim, sig, ch), 1)

    def population_round(setup, solver):
        ds, _, ch, scfg = setup
        make_population_round(ds, _sim(solver=solver, population=()), scfg,
                              ch, heterogeneous_sigmas(N))

    def schedule_chunk_runner(setup, solver):
        _, _, ch, scfg = setup
        make_schedule_chunk_runner(heterogeneous_sigmas(N), scfg, ch,
                                   solver=solver)

    def grid(setup, solver):
        ds, params, ch, scfg = setup
        run_grid(jax.random.PRNGKey(0), params, ds, _sim(solver=solver),
                 scfg, ch, GridSpec(channels=("rayleigh",),
                                    sigma_dists=("homogeneous",),
                                    policies=("proposed",), seeds=(0,)))

    def sweep(setup, solver):
        _, _, ch, scfg = setup
        run_sweep(jax.random.PRNGKey(0), heterogeneous_sigmas(N), scfg, ch,
                  rounds=2, policies=("proposed",), solver=solver)

    def service(setup, solver):
        SchedulerService(solver=solver)

    return [chunk_runner, population_round, schedule_chunk_runner, grid,
            sweep, service]


@pytest.mark.parametrize("entry", _solver_entry_points(),
                         ids=lambda f: f.__name__)
def test_pallas_solver_rejected_at_every_entry_point(small_setup, entry):
    """Every entry point refuses a solver outside ``SOLVERS`` — the
    retired ``"pallas"`` included — and names both valid values."""
    with pytest.raises(ValueError, match="'jnp', 'pallas_fused'"):
        entry(small_setup, "pallas")


def test_run_sweep_shapes_and_policy_ordering(small_setup):
    """One compiled call covers policies x seeds; the proposed policy beats
    M-matched uniform on communication time under heterogeneous channels
    (the Fig. 2/4 headline) and uniform sits at the power budget (Fig. 5)."""
    _, _, ch, scfg = small_setup
    sig = heterogeneous_sigmas(N)
    rounds, seeds = 60, (0, 1)
    sw = run_sweep(jax.random.PRNGKey(6), sig, scfg, ch, rounds=rounds,
                   seeds=seeds)
    for k in ("comm_time", "power", "avg_power", "n_selected"):
        assert sw[k].shape == (2, len(seeds), rounds), k
    assert sw["policies"] == ["proposed", "uniform"]
    # cumulative comm time is nondecreasing
    assert np.all(np.diff(sw["comm_time"], axis=-1) >= 0)
    assert np.all(sw["n_selected"] >= 1)
    prop, unif = sw["comm_time"][0, :, -1], sw["comm_time"][1, :, -1]
    assert np.mean(prop) < np.mean(unif), (prop, unif)
    # uniform allocates P = Pbar N / M', so per-round E[P q] sums to ~Pbar N
    np.testing.assert_allclose(sw["avg_power"][1, :, -1], ch.p_bar,
                               rtol=0.15)


def test_run_sweep_proposed_only_skips_matching(small_setup):
    _, _, ch, scfg = small_setup
    sig = heterogeneous_sigmas(N)
    sw = run_sweep(jax.random.PRNGKey(7), sig, scfg, ch, rounds=20,
                   policies=("proposed",))
    assert sw["comm_time"].shape == (1, 1, 20)
    with pytest.raises(ValueError):
        run_sweep(jax.random.PRNGKey(7), sig, scfg, ch, rounds=5,
                  policies=("greedy",))


def test_run_sweep_registry_policies_and_channels(small_setup):
    """All six registered policies sweep in one call, per-policy runners
    pruned; a temporally-correlated channel swaps in via the registry."""
    _, _, ch, scfg = small_setup
    sig = heterogeneous_sigmas(N)
    policies = ("proposed", "uniform", "greedy_channel",
                "proportional_gain", "update_aware", "aoi_capped")
    sw = run_sweep(jax.random.PRNGKey(8), sig, scfg, ch, rounds=30,
                   policies=policies, seeds=(0, 1),
                   channel="gauss_markov", channel_params=(("rho", 0.8),))
    assert sw["comm_time"].shape == (6, 2, 30)
    assert np.all(np.diff(sw["comm_time"], axis=-1) >= 0)
    assert np.all(sw["n_selected"] >= 1)
    # degenerate-q policies (greedy, aoi) report q = indicator, so their
    # per-round participation is ~m by construction
    m = float(sw["uniform_m"])
    assert abs(sw["n_selected"][2].mean() - round(m)) < 1.0
    # aoi's forced picks can exceed m when many clients hit the cap
    assert sw["n_selected"][5].mean() >= round(m) - 1.0


def _mask(n, lanes):
    sel = np.zeros(n, bool)
    sel[list(lanes)] = True
    return sel


def _fleet_mask(seed):
    """N = 10^5 at the fleet cell's density (about 870 of 10^6)."""
    return np.random.default_rng(seed).random(100_000) < 8.7e-4


_PACK_CASES = {
    "overflow": (lambda: _mask(20, [2, 3, 7, 11, 19]), 3),
    "fits": (lambda: _mask(20, [2, 3, 7, 11, 19]), 8),
    "none": (lambda: _mask(20, []), 8),
    "all": (lambda: np.ones(20, bool), 8),
    "exactly_m_cap": (lambda: _mask(20, [1, 4, 9, 16]), 4),
    "m_cap_plus_1": (lambda: _mask(20, [1, 4, 9, 16, 17]), 4),
    "lane_0": (lambda: _mask(20, [0]), 4),
    "last_lane": (lambda: _mask(20, [19]), 4),
    "fleet_seed_0": (lambda: _fleet_mask(0), 1024),
    "fleet_seed_1": (lambda: _fleet_mask(1), 1024),
    "fleet_seed_2": (lambda: _fleet_mask(2), 1024),
}


@pytest.mark.parametrize("case", list(_PACK_CASES))
def test_pack_participants_overflow(case):
    """The round's pack keeps the first ``m_cap`` selected clients in
    ascending order, zero-fills the rest, and counts those that did not
    fit: the same integers as ``np.flatnonzero`` and as the sized
    ``jnp.nonzero`` it replaced."""
    make, m_cap = _PACK_CASES[case]
    sel = make()
    idx, valid, overflow = pack_participants(sel, m_cap)
    want = np.flatnonzero(sel)[:m_cap]
    n_sel = int(sel.sum())
    assert idx.dtype == np.int32 and overflow.dtype == np.int32
    np.testing.assert_array_equal(idx[:len(want)], want)
    np.testing.assert_array_equal(idx[len(want):], 0)
    np.testing.assert_array_equal(valid, np.arange(m_cap) < n_sel)
    assert int(overflow) == max(n_sel - m_cap, 0)
    np.testing.assert_array_equal(
        idx, jnp.nonzero(sel, size=m_cap, fill_value=0)[0])


def test_pack_participants_has_no_scatter():
    """At the fleet width the pack lowers to gathers alone: no scatter-add
    of every lane (``jnp.nonzero``'s ``bincount``)."""
    sel = jax.ShapeDtypeStruct((1_000_000,), jnp.bool_)
    text = jax.jit(pack_participants, static_argnums=1).lower(
        sel, 1024).as_text()
    assert "gather" in text and "scatter" not in text
