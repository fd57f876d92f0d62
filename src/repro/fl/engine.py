"""Scan-compiled wireless-FL simulation engine — Figs. 2-5 at device speed.

The legacy engine (`repro.fl.simulation.run_simulation_loop`) drives every
round from a Python ``for`` loop: one jit dispatch per round plus a blocking
``float(t_comm)`` host sync, so at N=3597 FEMNIST scale the wall clock is
dominated by dispatch, not math. This module replaces the driver with
``jax.lax.scan`` and generalizes the round over the channel/policy
registries (``repro.core.channel``, ``repro.core.policies``):

* ``run_simulation`` runs the whole trajectory in ONE jitted call
  (:func:`run_config_chunks`): a 1-round chunk for the round-0 eval, a
  single ``lax.scan`` over the full ``eval_every``-round chunks, and a tail
  chunk — so at most three scan bodies compile regardless of length, all
  per-round accounting stays device-resident, and the host transfers four
  small arrays at the end. ``SimConfig.channel`` / ``SimConfig.policy``
  pick any registered fading model and selection policy.
* ``run_sweep`` vmaps the channel -> schedule -> select path over a batch of
  seeds per policy and scans all rounds in ONE compiled call per policy —
  the Fig. 2-5-style comparison (comm time, power, participation) without
  re-tracing per configuration, and without a mixed-policy body that pays
  for branches it discards (each per-policy runner is pruned to exactly
  that policy's ops).
* ``SimConfig.solver`` picks how the Theorem-2 decision runs, through
  ``repro.fl.decision.uses_fused``: ``"jnp"`` is the stitched closed form
  from ``repro.core.scheduler``; ``"pallas_fused"`` runs ``proposed``
  through the fused kernel ``kernels/decision_fused.py``, compiled on TPU
  and in interpret mode elsewhere, so the same config runs everywhere.
* ``SimConfig.model`` picks WHAT federates through the model registry
  (``repro.models.registry``: cnn | mlp | transformer_lm), and
  ``SimConfig.participant_shards`` picks HOW: 0 trains the sampled
  participants sequentially (``lax.map``); D >= 1 shards the participant
  axis over a D-device mesh (``fl/round.py::make_sharded_round_update``)
  with the Algorithm-1 aggregate as a cross-device psum — bitwise-equal to
  the sequential path at D=1 (tests/test_round_sharded.py). Setting BOTH
  ``client_shards=Dc`` and ``participant_shards=Dp`` composes the two on
  one shared (Dc, Dp) mesh ``('client', 'part')``: scheduling shards the
  client axis over the rows, local SGD the participant axis over the
  columns, and the all-gathered <= m_cap index pack is the only
  cross-stage traffic (``fl/sharding.py::make_mesh2d``,
  tests/test_mesh2d.py).

The multi-scenario grid (channel x sigma-distribution x policy x seed in a
single ``shard_map`` call across devices) lives in ``repro.fl.grid`` and is
built from the same round core (:func:`make_round_core`), so per-config grid
trajectories match :func:`run_simulation_scan` bit for bit.

The per-round decision pipeline itself (channel obs -> Theorem-2 solve ->
selection -> Z-update -> accounting) lives in ``repro.fl.decision`` and is
shared verbatim with the client-sharded runner and the multi-tenant online
scheduler service (``repro.service``); its scalar coefficients cross every
runner's jit boundary as RUNTIME ARGUMENTS (the operand contract,
``repro/core/scheduler.py``), which is what makes a served decision
bitwise-equal to an engine decision.

Round math is deliberately NOT shared with the legacy loop engine — the
parity test (tests/test_engine.py) checks two independent implementations
against each other on the same PRNG key.

Names in a profiler trace: the chunk runner's host span ``fl.dispatch``
(``repro.obs.span``) covers each call of the jitted chunk, and named
scopes put the round's device operations under ``fl.decision`` (channel
observation and decision), ``fl.update`` (packing, batch sampling, local
SGD, aggregation) and ``fl.eval`` (the chunk's evaluation) in their HLO
``op_name`` metadata. Scopes change metadata only, never a result.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (ChannelConfig, SchedulerConfig, channel_rate,
                        estimate_avg_selected, init_policy_state,
                        make_channel, make_policy)
from repro.core.policies import POLICY_IDS  # noqa: F401  (re-exported)
from repro.data.synthetic import FederatedDataset
from repro.fl.decision import (DecisionCoeffs, channel_obs, decision_coeffs,
                               decision_step, uses_fused)
from repro.fl.round import (local_sgd, make_sharded_round_update,
                            masked_aggregate, pack_participants,
                            sample_batches)
from repro.models.registry import make_model
from repro.obs import metrics as obs_metrics
from repro.obs.instrument import EngineInstruments, perf
from repro.obs.profile import span

# fold_in tag consumed by stateful channel inits (keeps the round-key chain
# identical to the stateless models', so rayleigh trajectories are unchanged)
CHANNEL_INIT_TAG = 0x6368  # "ch"


@dataclasses.dataclass
class SimConfig:
    """One simulated experiment (paper Section VI defaults)."""

    rounds: int = 200
    gamma: float = 0.01          # paper: 0.01
    local_steps: int = 10        # I
    batch: int = 32
    m_cap: int = 32              # max simulated participants per round
    eval_every: int = 10
    eval_size: int = 2000
    policy: str = "proposed"     # any repro.core.policies.POLICIES name
    aggregation: str = "paper"   # paper (Alg.1 l.7) | delta (variance-reduced)
    uniform_m: float = 0.0       # matched M for the baseline policies
    seed: int = 0
    engine: str = "scan"         # scan (compiled chunks) | loop (legacy)
    solver: str = "jnp"          # jnp closed form | pallas_fused (the
                                 # full-decision megakernel for
                                 # policy="proposed"; other policies fall
                                 # back to the stitched jnp path, which the
                                 # fused path is bitwise-equal to)
    channel: str = "rayleigh"    # any repro.core.channel.CHANNEL_MODELS name
    channel_params: tuple = ()   # ((name, value), ...) model extras
    policy_params: tuple = ()    # ((name, value), ...) policy extras
    model: str = "cnn"           # any repro.models.registry.MODELS name
    model_params: tuple = ()     # ((name, value), ...) model extras
    participant_shards: int = 0  # 0: sequential lax.map; D>=1: shard_map
                                 # the participant axis over D devices
    client_shards: int = 0       # 0: one-device (N,) scheduling; D>=1:
                                 # shard the CLIENT axis (channel step +
                                 # Theorem-2 solve + selection + queues)
                                 # over D devices (fl/client_shard.py).
                                 # Composes with participant_shards: both
                                 # set builds ONE shared (Dc, Dp) mesh
                                 # ('client', 'part') — scheduling shards
                                 # the rows, local SGD the columns
                                 # (fl/sharding.py::make_mesh2d)
    wire_dtype: str = "float32"  # delta-aggregation wire ("float32"|"bfloat16")
    population: Optional[tuple] = None
                                 # None: fixed fleet (the legacy engines,
                                 # untouched). ((name, value), ...) builds a
                                 # repro.fl.population.PopulationConfig —
                                 # Markov churn + straggler failures over an
                                 # activity mask; () is the degenerate
                                 # all-active scenario, bitwise-equal to
                                 # None on mesh 1 (tests/test_population.py)


# --------------------------------------------------------------------------
# One simulated round (scan body).
# --------------------------------------------------------------------------

WIRE_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


def resolve_wire_dtype(name: str):
    """``SimConfig.wire_dtype`` -> jnp dtype (delta-aggregation wire)."""
    if name not in WIRE_DTYPES:
        raise ValueError(f"unknown wire_dtype {name!r} "
                         f"(want one of {sorted(WIRE_DTYPES)})")
    return WIRE_DTYPES[name]


def make_round_core(ds: FederatedDataset, sim: SimConfig,
                    scfg: SchedulerConfig, decision=None):
    """The channel/policy-agnostic round body shared by the scan engine and
    the shard_map grid.

    Returns ``round_core(channel_step, policy_step, acct, params,
    pol_state, ch_state, key) -> (params, pol_state, ch_state, t_comm,
    power, n_sel)`` where ``channel_step(key, state) -> (gains, state)`` and
    ``policy_step(key, gains, state) -> (sel, q, p, state)`` come from the
    registries (bound per cell by the grid) and ``acct`` is the runtime
    ``AccountCoeffs`` bundle (the operand contract — see
    ``repro/fl/decision.py``). Key-split order and all accounting mirror
    the legacy engine exactly, so grid, scan, and loop trajectories agree
    on common configurations.

    What trains is ``sim.model`` resolved through the model registry
    (``repro.models.registry``). ``sim.participant_shards >= 1`` routes the
    local-SGD + aggregate through the participant-sharded ``shard_map``
    update (``fl/round.py::make_sharded_round_update``); 0 keeps the
    sequential ``lax.map`` path. The two are bitwise-equal at mesh size 1
    (tests/test_round_sharded.py documents the per-mesh contract).

    ``decision`` swaps the decision layer itself (default
    :func:`repro.fl.decision.decision_step`): ``solver="pallas_fused"``
    passes the fused-megakernel drop-in built by
    ``fl/decision.py::make_fused_decision``, which ignores ``policy_step``
    and runs solve + selection + Eq. 9 + accounting in one Pallas pass —
    bitwise-equal to the stitched default (tests/test_decision_fused.py).
    """
    n = ds.n_clients
    m_cap = sim.m_cap
    spec = make_model(sim.model, ds, **dict(sim.model_params))
    wire = resolve_wire_dtype(sim.wire_dtype)
    if sim.client_shards:
        raise ValueError(
            "make_round_core builds the single-device-client round; "
            "client_shards needs fl/client_shard.py's round (make_sim_round "
            "dispatches)")
    if sim.population is not None:
        raise ValueError(
            "make_round_core builds the fixed-fleet round; sim.population "
            "needs fl/population.py's masked round (make_sim_round "
            "dispatches)")
    sharded_update = None
    if sim.participant_shards:
        sharded_update = make_sharded_round_update(
            spec.loss_fn, sim.gamma, sim.local_steps, n,
            sim.participant_shards, aggregation=sim.aggregation,
            wire_dtype=wire)
    if decision is None:
        decision = decision_step

    def round_core(channel_step, policy_step, acct, params, pol_state,
                   ch_state, key):
        k_ch, k_sel, k_bat = jax.random.split(key, 3)
        # The observation + decision + accounting pipeline is the shared
        # decision layer (repro/fl/decision.py) — the exact function the
        # scheduler service serves online, which is what the service's
        # bitwise-parity contract rests on.
        with jax.named_scope("fl.decision"):
            gains, ch_state = channel_obs(channel_step, k_ch, ch_state)
            sel, q, p, t_comm, power, n_sel, pol_state = decision(
                policy_step, acct, k_sel, gains, pol_state)
        with jax.named_scope("fl.update"):
            # pick up to m_cap participants, packed left in ascending order
            sel_idx, sel_valid, _ = pack_participants(sel, m_cap)
            q_sel = q[sel_idx]
            imgs, labs = sample_batches(k_bat, ds.client_images,
                                        ds.client_labels, sel_idx, m_cap,
                                        sim.local_steps, sim.batch)
            if sharded_update is not None:
                new_params = sharded_update(params, imgs, labs, sel_valid,
                                            q_sel)
            else:
                # lax.map, not vmap: vmapped convs over per-client weights
                # lower to grouped convolutions (~30x slower on XLA:CPU).
                updated = jax.lax.map(
                    lambda b: local_sgd(spec.loss_fn, params, b, sim.gamma,
                                        sim.local_steps), (imgs, labs))
                new_params = masked_aggregate(params, updated, sel_valid,
                                              q_sel, n, sim.aggregation,
                                              wire)
        return (new_params, pol_state, ch_state, t_comm, power, n_sel)

    return round_core


def resolve_fused_decision(sim: SimConfig, scfg: SchedulerConfig, co):
    """``solver="pallas_fused"`` -> the megakernel decision drop-in, else
    None (callers then keep :func:`repro.fl.decision.decision_step`).

    Only ``policy="proposed"`` has a fused kernel; every other policy
    silently keeps the stitched path — safe because the fused path is
    bitwise-equal to it, so a policy grid mixing both stays coherent.
    ``co`` may hold traced leaves (the engines call this inside jit with
    the runtime bundle — the operand contract).
    """
    if uses_fused(sim.solver, sim.policy):
        from repro.fl.decision import make_fused_decision
        return make_fused_decision(scfg, co)
    return None


def make_sim_round(ds: FederatedDataset, sim: SimConfig,
                   scfg: SchedulerConfig, ch: ChannelConfig,
                   sigmas: jax.Array,
                   coeffs: Optional[DecisionCoeffs] = None):
    """Bind :func:`make_round_core` to one concrete channel model + policy.

    Returns ``sim_round(params, pol_state, ch_state, key)``— pure,
    scan-able. The channel comes from ``sim.channel`` / ``sim.channel_params``
    and the policy from ``sim.policy`` (matched M = ``sim.uniform_m``), both
    resolved through the registries. ``sim.client_shards >= 1`` routes the
    whole scheduling pipeline through the client-sharded ``shard_map`` path
    (``fl/client_shard.py``) — bitwise-identical at mesh size 1, exact
    accounting island on any mesh (tests/test_client_sharded.py).

    ``coeffs`` is the decision layer's scalar bundle. The engine runners
    call this INSIDE their jitted entry points with the traced bundle
    (operand contract, ``repro/fl/decision.py``); the default builds host
    constants for standalone use (benchmarks' legacy drive pattern).
    """
    co = coeffs if coeffs is not None else decision_coeffs(scfg, ch)
    if sim.client_shards:
        from repro.fl.client_shard import make_client_sharded_round
        return make_client_sharded_round(ds, sim, scfg, ch, sigmas,
                                         coeffs=co)
    if sim.population is not None:
        from repro.fl.population import make_population_round
        return make_population_round(ds, sim, scfg, ch, sigmas, coeffs=co)
    channel = make_channel(sim.channel, sigmas, ch,
                           **dict(sim.channel_params))
    policy_step = make_policy(sim.policy, scfg, ch, m_avg=sim.uniform_m,
                              coeffs=co.solve, **dict(sim.policy_params))
    round_core = make_round_core(ds, sim, scfg,
                                 decision=resolve_fused_decision(sim, scfg,
                                                                 co))

    def sim_round(params, pol_state, ch_state, key):
        return round_core(channel.step, policy_step, co.acct, params,
                          pol_state, ch_state, key)

    return sim_round


def eval_rounds(rounds: int, eval_every: int) -> list:
    """The rounds at which both engines record history."""
    return [r for r in range(rounds)
            if r % eval_every == 0 or r == rounds - 1]


# --------------------------------------------------------------------------
# Scan engine.
# --------------------------------------------------------------------------

def make_eval_fn(ds: FederatedDataset, sim: SimConfig):
    """Test-set accuracy of ``sim.model`` on the (static) eval slice."""
    spec = make_model(sim.model, ds, **dict(sim.model_params))
    ev_inputs = ds.test_images[: sim.eval_size]
    ev_labels = ds.test_labels[: sim.eval_size]

    def eval_fn(params):
        return spec.eval_fn(params, ev_inputs, ev_labels)

    return eval_fn


def scan_chunk(sim_round, eval_fn, carry, n_rounds: int):
    """Scan ``sim_round`` ``n_rounds`` times and evaluate — the chunk body
    shared (traced inline) by :func:`make_chunk_runner` and the grid."""

    def body(c, _):
        params, pst, cst, key, t_cum, p_cum = c
        key, k = jax.random.split(key)
        params, pst, cst, t_comm, power, nsel = sim_round(params, pst, cst,
                                                          k)
        return (params, pst, cst, key, t_cum + t_comm, p_cum + power), nsel

    carry, nsel = jax.lax.scan(body, carry, None, length=n_rounds)
    with jax.named_scope("fl.eval"):
        acc = eval_fn(carry[0])
    return carry, acc, nsel[-1]


def make_chunk_runner(ds: FederatedDataset, sim: SimConfig,
                      scfg: SchedulerConfig, ch: ChannelConfig,
                      sigmas: jax.Array):
    """Build the jitted multi-round chunk function behind the scan engine.

    ``run_chunk(carry, n_rounds)`` scans ``sim_round`` ``n_rounds`` times
    (static, so at most a few compiled variants), evaluates test accuracy on
    the resulting params, and returns ``(carry, acc, last_n_selected)``.
    ``carry = (params, pol_state, ch_state, key, t_comm_cum, power_cum)``
    and is donated — all accounting stays device-resident between eval
    points.

    Exposed separately from :func:`run_simulation_scan` so callers that
    drive many simulations (benchmarks, sweeps over checkpoints) can build
    once, warm each chunk length, and reuse the compiled function.

    The decision-layer coefficient bundle and the dataset cross the jit
    boundary as runtime arguments (supplied by the returned wrapper): the
    dataset so that it is not baked into the program, the bundle by the
    operand contract that makes the engine's per-round decisions
    bitwise-equal to the multi-tenant service's (``repro/fl/decision.py``).

    Telemetry (``repro.obs``, follows the process-wide ``configure``
    switch): each chunk length's first call counts an
    ``engine_compile_misses_total`` miss (``n_rounds`` is static, so a
    new length IS a fresh compile); with telemetry ON each chunk also
    records its wall time and the post-chunk Z-queue summary gauges
    (Eq. 9) — that pull synchronizes on the chunk result, trading the
    async overlap for live queue visibility, and changes no numerics
    (the returned carry is bitwise the same; tests/test_obs.py).
    """
    co_host = decision_coeffs(scfg, ch)
    ei = EngineInstruments(obs_metrics.default_registry())

    @functools.partial(jax.jit, static_argnames=("n_rounds",),
                       donate_argnums=(0,))
    def _run_chunk(carry, co, data, n_rounds):
        sim_round = make_sim_round(data, sim, scfg, ch, sigmas, coeffs=co)
        return scan_chunk(sim_round, make_eval_fn(data, sim), carry,
                          n_rounds)

    def run_chunk(carry, n_rounds):
        fresh = ei.compiles.miss(("run_chunk", n_rounds),
                                 entry="run_chunk", n_rounds=n_rounds)
        t0 = perf()
        with span("fl.dispatch"):
            carry, acc, nsel = _run_chunk(carry, co_host, ds, n_rounds)
        if fresh:
            # jit traces + compiles synchronously at call time
            ei.compiles.compile_s.inc(perf() - t0)
        if ei.enabled:
            ei.record_policy_state(carry[1])   # syncs: chunk truly done
            ei.chunk_s.record(perf() - t0)
        return carry, acc, nsel

    return run_chunk


def init_channel_carry(key, sim: SimConfig, channel, n_clients: int):
    """The channel-state carry slot off the config key's side-channels.

    The model's stationary init consumes ``fold_in(key, CHANNEL_INIT_TAG)``;
    with ``sim.population`` set the slot becomes the ``(ch_state, active)``
    pair the population round carries, the round-0 mask coming off
    ``POP_INIT_TAG`` — both side-channels, so the round-key chain is
    identical in every configuration.
    """
    ch0 = channel.init(jax.random.fold_in(key, CHANNEL_INIT_TAG))
    if sim.population is None:
        return ch0
    from repro.fl.population import init_active_mask, population_config
    return (ch0, init_active_mask(key, n_clients,
                                  population_config(sim.population)))


def init_carry(key, params, scfg: SchedulerConfig, sim: SimConfig, sigmas,
               ch: ChannelConfig):
    """Fresh scan-engine carry (copies params: chunks donate their input).

    The policy state and channel model come from the same ``sim`` /
    ``sigmas`` / ``ch`` the chunk runner was built with — they are required
    so a stateful fading model (e.g. ``gauss_markov``) always gets its
    stationary init instead of a silently-wrong zero state. The channel
    init consumes ``fold_in(key, CHANNEL_INIT_TAG)``, a side-channel of
    the main key, so memoryless models leave the round-key chain untouched.
    """
    channel = make_channel(sim.channel, sigmas, ch,
                           **dict(sim.channel_params))
    return (jax.tree.map(jnp.array, params),
            init_policy_state(sim.policy, scfg.n_clients),
            init_channel_carry(key, sim, channel, scfg.n_clients), key,
            jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32))


def run_config_chunks(sim_round, eval_fn, rounds: int, eval_every: int,
                      params, pol_state, ch_state, key):
    """The whole-trajectory chunk schedule, traced into ONE program.

    Chunk structure: a 1-round chunk (eval at round 0), then a single
    ``lax.scan`` over the full ``eval_every``-round chunks, then the tail
    chunk if the final round is not on the eval stride — so at most three
    scan bodies compile regardless of trajectory length, matching
    :func:`eval_rounds` exactly. Returns stacked per-eval-point arrays
    ``(comm_cum, test_acc, power_cum, n_selected)``, each (E,).

    This function is THE per-config program of both
    :func:`run_simulation_scan` and the shard_map grid
    (``repro.fl.grid``) — sharing the trace end to end is what makes grid
    trajectories bitwise-equal to per-config runs (XLA fuses structurally
    different programs differently, drifting f32 results by ulps).
    """
    carry = (params, pol_state, ch_state, key,
             jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32))
    carry, acc0, ns0 = scan_chunk(sim_round, eval_fn, carry, 1)
    first = (carry[4], acc0, carry[5], ns0)
    n_full = (rounds - 1) // eval_every
    parts = [jax.tree.map(lambda x: x[None], first)]
    if n_full > 0:
        def outer(c, _):
            c, acc, nsel = scan_chunk(sim_round, eval_fn, c, eval_every)
            return c, (c[4], acc, c[5], nsel)

        carry, mids = jax.lax.scan(outer, carry, None, length=n_full)
        parts.append(mids)
    tail = (rounds - 1) - n_full * eval_every
    if tail > 0:
        carry, acc_t, ns_t = scan_chunk(sim_round, eval_fn, carry, tail)
        parts.append(jax.tree.map(lambda x: x[None],
                                  (carry[4], acc_t, carry[5], ns_t)))
    return tuple(jnp.concatenate([p[i] for p in parts])
                 for i in range(4))


def make_config_runner(ds: FederatedDataset, sim: SimConfig,
                       scfg: SchedulerConfig, ch: ChannelConfig,
                       sigmas: jax.Array):
    """Jit the full single-config trajectory: ``runner(params, key) ->
    (comm_cum, test_acc, power_cum, n_selected)``, each (E,).

    The coefficient bundle rides the jit boundary as a runtime argument
    (operand contract, ``repro/fl/decision.py``), and so does the dataset,
    which would otherwise be baked into the program as a constant."""
    channel = make_channel(sim.channel, sigmas, ch,
                           **dict(sim.channel_params))
    n = scfg.n_clients
    co_host = decision_coeffs(scfg, ch)

    @jax.jit
    def _runner(params, key, co, data):
        sim_round = make_sim_round(data, sim, scfg, ch, sigmas, coeffs=co)
        pol0 = init_policy_state(sim.policy, n)
        ch0 = init_channel_carry(key, sim, channel, n)
        return run_config_chunks(sim_round, make_eval_fn(data, sim),
                                 sim.rounds, sim.eval_every, params, pol0,
                                 ch0, key)

    def runner(params, key):
        return _runner(params, key, co_host, ds)

    return runner


def history_from_trajectory(rounds: int, eval_every: int, n_clients: int,
                            comm, acc, pcum, nsel) -> Dict[str, np.ndarray]:
    """Per-eval-point device arrays -> the engines' history dict layout
    (float64 host math for avg_power, as the legacy loop computes it)."""
    ev = np.asarray(eval_rounds(rounds, eval_every))
    return {
        "round": ev,
        "comm_time": np.asarray(comm).astype(np.float64),
        "test_acc": np.asarray(acc).astype(np.float64),
        "avg_power": (np.asarray(pcum).astype(np.float64)
                      / (ev + 1) / n_clients),
        "n_selected": np.asarray(nsel).astype(np.int64),
    }


def run_simulation_scan(key, params, ds: FederatedDataset, sim: SimConfig,
                        scfg: SchedulerConfig, ch: ChannelConfig,
                        sigmas: jax.Array) -> Dict[str, np.ndarray]:
    """Scan-compiled drop-in for the legacy ``run_simulation`` loop.

    The whole trajectory — every eval-interval chunk — runs in ONE jitted
    call with all accounting device-resident; the host transfers four small
    arrays at the end instead of two scalars per round. History layout
    (round / comm_time / test_acc / avg_power / n_selected) matches the
    legacy engine. Any registered channel model and policy is accepted
    (the legacy loop knows only rayleigh + proposed/uniform).

    With process-wide telemetry on (``repro.obs.configure(True)``) the
    run records rounds/s, per-interval comm-time deltas (Eq. 8), and
    selection counts against the default registry — all computed from
    the already-materialized history arrays AFTER the compiled call, so
    the trajectory is bitwise-identical either way (tests/test_obs.py).
    """
    ei = EngineInstruments(obs_metrics.default_registry())
    t0 = perf()
    runner = make_config_runner(ds, sim, scfg, ch, sigmas)
    # a fresh runner is jitted per call, so every run pays one compile
    ei.compiles.miss(("config_runner", sim.rounds), entry="config_runner",
                     policy=sim.policy, rounds=sim.rounds)
    comm, acc, pcum, nsel = runner(params, key)
    hist = history_from_trajectory(sim.rounds, sim.eval_every,
                                   ds.n_clients, comm, acc, pcum, nsel)
    if ei.enabled:
        ei.record_history(hist, perf() - t0)   # host arrays: already sync
    return hist


# --------------------------------------------------------------------------
# Policy x seed sweep: the Fig. 2-5 comparison, one compiled call per policy.
# --------------------------------------------------------------------------

def make_sweep_runner(sigmas: jax.Array, scfg: SchedulerConfig,
                      ch: ChannelConfig, *, rounds: int,
                      policy: str = "proposed", m_avg: float = 1.0,
                      channel: str = "rayleigh", channel_params: tuple = (),
                      guarantee_one: bool = True,
                      policy_params: Optional[dict] = None):
    """Build the jitted batched scheduling-trajectory function for ONE policy.

    Returns ``runner(seed_keys)`` mapping a (S, 2) batch of PRNG keys to
    per-seed trajectories ``(comm_cum, power, avg_power, n_selected)``, each
    (S, rounds). The whole channel -> solve -> select -> account chain
    compiles into one scan body, so XLA fuses the elementwise work and
    per-round dispatch disappears.

    One runner per policy (rather than a flag-switched mixed body) means a
    config never computes a branch it discards — a proposed-only sweep never
    pays the uniform baseline's O(N log N) sort, and vice versa.
    """
    n = scfg.n_clients
    scfg_run = dataclasses.replace(scfg, guarantee_one=guarantee_one)
    chan = make_channel(channel, sigmas, ch, **dict(channel_params))
    co_host = decision_coeffs(scfg_run, ch)

    def one_seed(cfg_key, co):
        # the policy binds to the runtime coefficient bundle like every
        # other engine (the operand contract, repro/fl/decision.py); the
        # sweep's own lightweight accounting (plain sums, not the blocked
        # reduce) is deliberately kept — it is statistical output, not
        # part of any bitwise contract
        step = make_policy(policy, scfg_run, ch, m_avg=m_avg,
                           coeffs=co.solve, **(policy_params or {}))

        def body(carry, k):
            pst, cst = carry
            k_ch, k_sel = jax.random.split(k)
            gains, cst = chan.step(k_ch, cst)
            sel, q, p, pst = step(k_sel, gains, pst)
            rate = channel_rate(gains, p, ch)
            t_comm = jnp.sum(jnp.where(sel, scfg.model_bits
                                       / jnp.maximum(rate, 1e-9), 0.0))
            power = jnp.sum(p * q)
            return (pst, cst), (t_comm, power, jnp.sum(sel))

        cst0 = chan.init(jax.random.fold_in(cfg_key, CHANNEL_INIT_TAG))
        round_keys = jax.random.split(cfg_key, rounds)
        _, (t_comm, power, nsel) = jax.lax.scan(
            body, (init_policy_state(policy, n), cst0), round_keys)
        denom = jnp.arange(1, rounds + 1, dtype=jnp.float32)
        return (jnp.cumsum(t_comm), power, jnp.cumsum(power) / denom / n,
                nsel)

    _runner = jax.jit(
        lambda seed_keys, co: jax.vmap(lambda k: one_seed(k, co))(
            seed_keys))
    return lambda seed_keys: _runner(seed_keys, co_host)


def run_sweep(key, sigmas: jax.Array, scfg: SchedulerConfig,
              ch: ChannelConfig, *, rounds: int,
              policies: Sequence[str] = ("proposed", "uniform"),
              seeds: Sequence[int] = (0,), uniform_m: Optional[float] = None,
              solver: str = "jnp", guarantee_one: bool = True,
              match_rounds: int = 300, channel: str = "rayleigh",
              channel_params: tuple = (),
              policy_params: Optional[Dict[str, dict]] = None
              ) -> Dict[str, np.ndarray]:
    """Batched channel -> schedule -> select sweep over policies x seeds.

    Every configuration's full ``rounds``-round trajectory — fading draws
    (any registered ``channel``), the policy's selection rule, Eq. (9)
    queue updates where applicable, TDMA comm-time and power accounting —
    runs under one ``jit(vmap(scan))`` per policy, each pruned to exactly
    that policy's ops. Model training is excluded (that is
    ``run_simulation``'s job); this is the scheduling-layer comparison behind
    the comm-time / power / participation axes of Figs. 2-5.

    Returns arrays of shape (len(policies), len(seeds), rounds):
    ``comm_time`` (cumulative seconds), ``power`` (per-round sum P q),
    ``avg_power`` (running mean of sum P q / N, the Fig. 5 trajectory),
    ``n_selected``, plus the scalar ``uniform_m`` used for matching.
    """
    from repro.core.policies import POLICIES

    unknown = [p for p in policies if p not in POLICIES]
    if unknown:
        raise ValueError(f"unknown policies {unknown} "
                         f"(registered: {sorted(POLICIES)})")
    # the sweep keeps its own plain-sum accounting, so every policy runs
    # the stitched step under either solver; the name is still checked,
    # before the matched-M Monte Carlo
    uses_fused(solver, "proposed")
    needs_m = any(POLICIES[p][2] for p in policies)
    if uniform_m is None:
        if needs_m:
            # M is matched under the channel actually being swept — a
            # Rayleigh-only Monte Carlo would mis-match every baseline on
            # rician/lognormal/gauss_markov sweeps
            chan = (None if channel == "rayleigh" else
                    make_channel(channel, sigmas, ch, **dict(channel_params)))
            uniform_m = float(estimate_avg_selected(
                jax.random.fold_in(key, 7), sigmas, scfg, ch, match_rounds,
                channel=chan))
        else:
            uniform_m = 1.0

    # fold_in per seed, shared across policies: same seed -> same channel and
    # selection randomness, the paired comparison the paper plots.
    seed_keys = jnp.stack([jax.random.fold_in(key, s) for s in seeds])

    per_policy = []
    for p in policies:
        runner = make_sweep_runner(
            sigmas, scfg, ch, rounds=rounds, policy=p, m_avg=uniform_m,
            channel=channel, channel_params=channel_params,
            guarantee_one=guarantee_one,
            policy_params=(policy_params or {}).get(p))
        per_policy.append(runner(seed_keys))

    comm, power, avg_power, nsel = [
        np.stack([np.asarray(r[i]) for r in per_policy]) for i in range(4)]
    return {
        "policies": list(policies),
        "seeds": np.asarray(seeds),
        "uniform_m": np.float32(uniform_m),
        "comm_time": comm,
        "power": power,
        "avg_power": avg_power,
        "n_selected": nsel,
    }
