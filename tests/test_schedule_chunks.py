"""The stepping scheduling runner (``fl/client_shard.py``).

``make_schedule_chunk_runner`` steps the scheduling layer chunk by chunk
from a carry of ``init_schedule_carry``; ``make_schedule_runner`` scans
the same rounds from fresh queues and returns the sums alone. Pinned
here: chunks stepped with the carry are that one trajectory bit for bit;
each round's ids are the first ``m_cap`` selected clients, ascending;
the overflow counts the rest; a chunk length compiles once; the unpack
sums the overflow into ``fl_schedule_overflow_total``; the whole
trajectory compiles without the id pack; the coefficients live on the
device.
"""

import re

import jax
import numpy as np
import pytest

from repro import obs
from repro.core import (ChannelConfig, SchedulerConfig, heterogeneous_sigmas,
                        make_channel, make_policy)
from repro.fl.client_shard import (init_schedule_carry,
                                   make_schedule_chunk_runner,
                                   make_schedule_runner)
from repro.fl.decision import channel_obs, decision_coeffs, decision_step

N = 2000
CAP = 64


@pytest.fixture(autouse=True)
def _default_off():
    yield
    obs.configure(False)


@pytest.fixture(scope="module")
def fleet():
    scfg = SchedulerConfig(n_clients=N, model_bits=32 * 555178.0)
    ch = ChannelConfig(n_clients=N)
    return heterogeneous_sigmas(N), scfg, ch


def _stepped(fleet, key, k, m, **kw):
    sig, scfg, ch = fleet
    run_chunk = make_schedule_chunk_runner(sig, scfg, ch, **kw)
    carry = init_schedule_carry(key, sig, ch)
    rows = []
    for _ in range(m):
        carry, out = run_chunk(carry, k)
        rows.append(run_chunk.unpack(out))
    return {name: np.concatenate([r[name] for r in rows])
            for name in rows[0]}


@pytest.mark.parametrize("solver,shards", [("jnp", 0), ("pallas_fused", 0),
                                           ("jnp", 1)])
def test_chunks_stepped_with_the_carry_are_one_trajectory(fleet, solver,
                                                          shards):
    sig, scfg, ch = fleet
    key = jax.random.PRNGKey(3)
    kw = dict(solver=solver, client_shards=shards, m_cap=CAP)
    whole = make_schedule_runner(sig, scfg, ch, rounds=6, **kw)(key)
    stepped = _stepped(fleet, key, 2, 3, **kw)
    for name, x in zip(("t_comm", "power", "n_sel"), whole):
        assert x.dtype == stepped[name].dtype
        np.testing.assert_array_equal(x, stepped[name], err_msg=name)
    # the key the caller passed stays usable (the carry holds a copy)
    assert make_schedule_runner(sig, scfg, ch, rounds=1, **kw)(key)[2] > 0


def _selections(fleet, key, rounds):
    """Each round's selection mask, from the decision layer stepped
    directly on the runner's key chain."""
    sig, scfg, ch = fleet
    chan = make_channel("rayleigh", sig, ch)

    @jax.jit
    def one(pst, cst, k, co):
        k, kr = jax.random.split(k)
        k_ch, k_sel, _ = jax.random.split(kr, 3)
        step = make_policy("proposed", scfg, ch, coeffs=co.solve)
        gains, cst = channel_obs(chan.step, k_ch, cst)
        sel, *_, pst = decision_step(step, co.acct, k_sel, gains, pst)
        return pst, cst, k, sel

    pst, cst, k = init_schedule_carry(key, sig, ch)
    co = decision_coeffs(scfg, ch)
    sels = []
    for _ in range(rounds):
        pst, cst, k, sel = one(pst, cst, k, co)
        sels.append(np.asarray(sel))
    return np.array(sels)


@pytest.mark.parametrize("cap", [CAP, 8])
def test_ids_are_the_first_selected_and_overflow_the_rest(fleet, cap):
    key = jax.random.PRNGKey(4)
    sels = _selections(fleet, key, 4)
    rows = _stepped(fleet, key, 4, 1, m_cap=cap)
    n_sel = sels.sum(axis=1)
    assert np.all(n_sel > 8)          # cap 8 lies below every selection
    np.testing.assert_array_equal(rows["n_sel"], n_sel)
    np.testing.assert_array_equal(rows["overflow"],
                                  n_sel - np.minimum(n_sel, cap))
    for sel, ids in zip(sels, rows["ids"]):
        want = np.flatnonzero(sel)[:cap]
        np.testing.assert_array_equal(ids[:want.size], want)
        assert not ids[want.size:].any()


def test_a_chunk_length_compiles_once(fleet):
    from jax._src import monitoring
    sig, scfg, ch = fleet
    run_chunk = make_schedule_chunk_runner(sig, scfg, ch, m_cap=CAP)
    carry = init_schedule_carry(jax.random.PRNGKey(5), sig, ch)
    carry, out = run_chunk(carry, 3)
    jax.block_until_ready(out)
    compiles = []

    def on(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)
    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        carry, out = run_chunk(carry, 3)
        jax.block_until_ready(out)
    finally:
        monitoring.unregister_event_duration_listener(on)
    assert compiles == []


def test_unpack_counts_overflow_and_chunk_lengths_miss(fleet):
    sig, scfg, ch = fleet
    reg = obs.configure(True)
    run_chunk = make_schedule_chunk_runner(sig, scfg, ch, m_cap=8)
    carry = init_schedule_carry(jax.random.PRNGKey(6), sig, ch)
    total = 0
    for k in (2, 2, 1):
        carry, out = run_chunk(carry, k)
        total += int(run_chunk.unpack(out)["overflow"].sum())
    assert total > 0
    assert reg.value("fl_schedule_overflow_total") == total
    assert reg.total("engine_compile_misses_total") == 2


@pytest.mark.parametrize("solver", ["jnp", "pallas_fused"])
def test_the_whole_trajectory_compiles_no_pack(fleet, solver):
    """``make_schedule_runner`` returns the sums alone, so its program
    keeps the decision and drops the id pack a chunk returns."""
    sig, scfg, ch = fleet
    runner = make_schedule_runner(sig, scfg, ch, rounds=2, solver=solver,
                                  m_cap=CAP)
    hlo = jax.jit(runner).lower(jax.random.PRNGKey(7)).compile().as_text()
    assert re.search(r'op_name="([^"]*/)?fl\.decision/', hlo)
    assert not re.search(r'op_name="([^"]*/)?fl\.pack/', hlo)


def test_coefficients_go_to_the_device_once(fleet):
    sig, scfg, ch = fleet
    run_chunk = make_schedule_chunk_runner(sig, scfg, ch, m_cap=CAP)
    leaves = jax.tree.leaves(run_chunk.co)
    assert leaves and all(isinstance(x, jax.Array) for x in leaves)
