"""End-to-end wireless-FL simulation — the engine behind Figs. 2-5.

Couples all the substrates: Rayleigh channel draws -> Algorithm-2 scheduling
(or the M-matched uniform baseline) -> Algorithm-1 federated round on any
registered model (``SimConfig.model``: the paper's CNN, an MLP, or the
transformer LM — ``repro.models.registry``) -> TDMA communication-time
accounting. Computation time is excluded from the clock, as in Section VI
("we assume that the computation time is much less than communication
time").

``run_simulation`` dispatches on ``SimConfig.engine``:

* ``"scan"`` (default) — the lax.scan-compiled engine in ``repro.fl.engine``:
  rounds between eval points run in one compiled chunk, accounting stays
  device-resident, host syncs only at eval points.
* ``"loop"`` — the legacy per-round Python loop below, kept as an
  independently-implemented reference: tests/test_engine.py checks the two
  engines produce the same history from the same PRNG key.

Memory note: only up to ``m_cap`` sampled participants are simulated per
round (Algorithm 1's aggregation takes zero contribution from everyone
else), so N=3597 FEMNIST clients never materialize 3597 model replicas.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (ChannelConfig, SchedulerConfig, channel_rate,
                        draw_gains, estimate_avg_selected, init_state,
                        schedule_step, uniform_selection)
from repro.data.synthetic import FederatedDataset
from repro.fl.engine import (SimConfig, resolve_wire_dtype,
                             run_simulation_scan, run_sweep)
from repro.fl.round import local_sgd
from repro.models.registry import make_model

__all__ = ["SimConfig", "run_simulation", "run_simulation_loop",
           "run_simulation_scan", "run_sweep", "match_uniform_m",
           "time_to_accuracy"]


def _select_proposed(key, gains, sched_state, scfg, ch):
    sel, q, p, new_state = schedule_step(key, gains, sched_state, scfg, ch)
    return sel, q, p, new_state


def _round_update(loss_fn, params, sel_idx, sel_valid, q_sel, batches, gamma,
                  steps, n_clients, aggregation="paper",
                  wire_dtype=jnp.float32):
    """Aggregate x <- (1/N) sum_{i in sel} (1/q_i) y_i over <= m_cap clients
    (paper), or the variance-reduced delta form x + (1/N) sum (1/q)(y - x)
    whose summand is cast to ``wire_dtype`` before the reduce (the bf16
    wire design of fl/round.py::delta_aggregate; float32 = historic math).

    Clients are iterated with lax.map (sequential) rather than vmap: vmapping
    convolutions over per-client weights lowers to grouped convolutions,
    which hit a ~30x slow path on XLA:CPU. Sequential keeps every conv on
    the fast kernel; on TPU the FL pod path uses vmap (repro/fl/round.py).
    """
    updated = jax.lax.map(
        lambda b: local_sgd(loss_fn, params, b, gamma, steps), batches)
    w = sel_valid.astype(jnp.float32) / jnp.maximum(q_sel, 1e-9) / n_clients

    if aggregation == "delta":
        def agg(x, y):
            wf = w.reshape((-1,) + (1,) * (y.ndim - 1))
            delta = y.astype(jnp.float32) - x.astype(jnp.float32)[None]
            update = jnp.sum((delta * wf).astype(wire_dtype), axis=0)
            return x.astype(jnp.float32) + update.astype(jnp.float32)

        return jax.tree.map(agg, params, updated)

    def agg(y):
        wf = w.reshape((-1,) + (1,) * (y.ndim - 1))
        return jnp.sum(y.astype(jnp.float32) * wf, axis=0)

    return jax.tree.map(agg, updated)


def run_simulation(key, params, ds: FederatedDataset, sim: SimConfig,
                   scfg: SchedulerConfig, ch: ChannelConfig,
                   sigmas: jax.Array) -> Dict[str, np.ndarray]:
    """Returns history dict: round, comm_time (cumulative s), test_acc,
    avg_power (per-round E[P q]), n_selected.

    Thin dispatcher: ``sim.engine`` picks the scan-compiled engine (default)
    or the legacy per-round loop; both return the same history layout.
    """
    if sim.engine == "scan":
        return run_simulation_scan(key, params, ds, sim, scfg, ch, sigmas)
    if sim.engine != "loop":
        raise ValueError(f"unknown engine {sim.engine!r} (want 'scan'|'loop')")
    if sim.channel != "rayleigh" or sim.policy not in ("proposed", "uniform"):
        raise ValueError(
            "the legacy loop engine only knows the paper's setup "
            "(channel='rayleigh', policy in {'proposed', 'uniform'}); use "
            "engine='scan' for registry channels/policies")
    if sim.participant_shards or sim.client_shards:
        raise ValueError(
            "the legacy loop engine is the sequential parity reference; "
            "participant/client sharding needs engine='scan'")
    if sim.population is not None:
        raise ValueError(
            "the legacy loop engine has no dynamic-population path; "
            "sim.population needs engine='scan'")
    return run_simulation_loop(key, params, ds, sim, scfg, ch, sigmas)


def run_simulation_loop(key, params, ds: FederatedDataset, sim: SimConfig,
                        scfg: SchedulerConfig, ch: ChannelConfig,
                        sigmas: jax.Array) -> Dict[str, np.ndarray]:
    """Legacy engine: one jit dispatch + host sync per round (the reference
    implementation the scan engine is tested against)."""
    n = ds.n_clients
    m_cap = sim.m_cap
    sched_state = init_state(scfg)
    spec = make_model(sim.model, ds, **dict(sim.model_params))
    wire = resolve_wire_dtype(sim.wire_dtype)
    # sim_round donates its params buffer; copy so callers keep theirs.
    params = jax.tree.map(jnp.array, params)

    @jax.jit
    def eval_acc(params, inputs, labels):
        return spec.eval_fn(params, inputs, labels)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def sim_round(params, sched_state, key):
        k_ch, k_sel, k_bat = jax.random.split(key, 3)
        gains = draw_gains(k_ch, sigmas, ch)
        if sim.policy == "proposed":
            sel, q, p, sched_state = _select_proposed(k_sel, gains,
                                                      sched_state, scfg, ch)
        else:
            sel, q, p = uniform_selection(k_sel, n, sim.uniform_m, ch)
        # --- comm time: TDMA sum over selected (Eq. 8 denominator) ---
        rate = channel_rate(gains, p, ch)
        t_comm = jnp.sum(jnp.where(sel, scfg.model_bits
                                   / jnp.maximum(rate, 1e-9), 0.0))
        power = jnp.sum(p * q)  # sum_n E[P_n q_n] this round
        # --- pick up to m_cap participants ---
        sel_idx = jnp.nonzero(sel, size=m_cap, fill_value=0)[0]
        sel_valid = jnp.arange(m_cap) < jnp.sum(sel)  # nonzero packs left
        q_sel = q[sel_idx]
        # --- local minibatches for the participants ---
        per_client = ds.client_labels.shape[1]
        idx = jax.random.randint(
            k_bat, (m_cap, sim.local_steps, sim.batch), 0, per_client)
        imgs = ds.client_images[sel_idx[:, None, None], idx]
        labs = ds.client_labels[sel_idx[:, None, None], idx]
        new_params = _round_update(spec.loss_fn, params, sel_idx, sel_valid,
                                   q_sel, (imgs, labs), sim.gamma,
                                   sim.local_steps, n, sim.aggregation,
                                   wire)
        return new_params, sched_state, t_comm, power, jnp.sum(sel)

    hist: Dict[str, List] = {"round": [], "comm_time": [], "test_acc": [],
                             "avg_power": [], "n_selected": []}
    t_cum = 0.0
    power_cum = 0.0
    key_loop = key
    ev_imgs = ds.test_images[: sim.eval_size]
    ev_labels = ds.test_labels[: sim.eval_size]
    for r in range(sim.rounds):
        key_loop, k = jax.random.split(key_loop)
        params, sched_state, t_comm, power, nsel = sim_round(
            params, sched_state, k)
        t_cum += float(t_comm)
        power_cum += float(power)
        if r % sim.eval_every == 0 or r == sim.rounds - 1:
            acc = float(eval_acc(params, ev_imgs, ev_labels))
            hist["round"].append(r)
            hist["comm_time"].append(t_cum)
            hist["test_acc"].append(acc)
            hist["avg_power"].append(power_cum / (r + 1) / n)
            hist["n_selected"].append(int(nsel))
    return {k: np.asarray(v) for k, v in hist.items()}


def match_uniform_m(key, sigmas, scfg: SchedulerConfig, ch: ChannelConfig,
                    rounds: int = 300, channel: str = "rayleigh",
                    channel_params: tuple = ()) -> float:
    """Estimate Algorithm 2's average participation M to configure the
    M-matched uniform baseline (paper Section VI's strong benchmark).

    ``channel`` picks the fading model the estimate runs under — match M
    against the channel you will actually sweep, or the "M-matched"
    baseline is matched to the wrong gain distribution. ``channel_params``
    are the registry extras (``k_factor``, ``shadow_db``, ``rho``); passing
    them with ``channel="rayleigh"`` is rejected rather than silently
    ignored (rayleigh takes none — a misspelled channel name would
    otherwise produce a silently mis-matched M).
    """
    from repro.core import make_channel
    from repro.core.channel import CHANNEL_MODELS

    if channel not in CHANNEL_MODELS:
        raise ValueError(f"unknown channel model {channel!r} "
                         f"(registered: {sorted(CHANNEL_MODELS)})")
    if channel == "rayleigh":
        if channel_params:
            raise ValueError(
                "channel='rayleigh' takes no channel_params; got "
                f"{dict(channel_params)!r} — did you mean a registry "
                "channel (rician/lognormal/gauss_markov)?")
        chan = None
    else:
        chan = make_channel(channel, sigmas, ch, **dict(channel_params))
    return float(estimate_avg_selected(key, sigmas, scfg, ch, rounds,
                                       channel=chan))


def time_to_accuracy(hist: Dict[str, np.ndarray], target: float
                     ) -> Optional[float]:
    """First cumulative comm time at which test_acc >= target.

    Returns None when the target is never reached, including for an empty
    history. Accepts plain-list histories (hand-built or JSON-roundtripped)
    as well as the engines' ndarray ones — a list crashed the ``>=`` before.
    """
    acc = np.asarray(hist["test_acc"], dtype=np.float64)
    if acc.size == 0:
        return None
    idx = np.nonzero(acc >= target)[0]
    if idx.size == 0:
        return None
    return float(np.asarray(hist["comm_time"], dtype=np.float64)[idx[0]])
