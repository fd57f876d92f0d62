"""Dynamic populations over a static-shape arena: churn + stragglers.

The paper derives Algorithm 2 for a fixed fleet of N devices, but its
headline property — only instantaneous CSI is needed — matters most when
the environment misbehaves: devices arrive and depart between rounds, and
selected devices fail mid-round so their updates never arrive. This module
makes both first-class, jit-static citizens of every engine:

* an **activity mask** over a max-N arena. Shapes never change under jit —
  a departed device keeps its lane, carrying a ``False`` bit in a (N,) bool
  mask that rides the channel-state slot of the scan carry as
  ``(ch_state, active)``. Arrival/departure is a per-lane two-state Markov
  chain (:func:`churn_step`): an active device departs w.p. ``p_leave``, an
  inactive lane (re)joins w.p. ``p_join``. At least one device is always
  kept active (mirroring the selection layer's ``guarantee_one`` fallback,
  which would otherwise force-select an inactive lane).
* **post-selection straggler failures**: each SELECTED device fails to
  deliver w.p. ``p_fail`` (:func:`failure_split`). Failures follow the
  timeout model — a failed device still burned its TDMA slot, so its
  airtime stays in ``t_comm`` and it still counts in ``n_selected``; only
  the training tail sees ``delivered = sel & ~failed``.
* the **Eq. 9 fence**: the Z queue is charged the *expected* power ``P q``
  at decision time (exactly the paper's update — Eq. 9 is an expectation
  over the Bernoulli selection, so a later delivery failure does NOT credit
  Z back), and an inactive lane has q masked to 0 *before* the update, so
  its queue drains by ``p_bar`` per round while away. The masking itself
  lives in the policy layer (``repro.core.policies``: every step takes
  optional ``(active, n_active)`` operands) so selection thresholds clip
  into the active count and can never tie into inactive sentinel lanes.

Randomness: the churn/failure draws consume ``fold_in`` side-channels of
the round key (tags below), so the engines' historic 3-way round-key split
``(k_ch, k_sel, k_bat)`` is untouched — with a degenerate
:class:`PopulationConfig` (no churn, no failures, everyone active) every
comparison the mask machinery adds is value-preserving per lane and the
trajectory is BITWISE-equal to the legacy engines (tests/test_population.py
asserts exact equality on mesh 1).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import SchedulerConfig, make_channel, make_policy
from repro.data.synthetic import FederatedDataset
from repro.fl.decision import (DecisionCoeffs, channel_obs, decision_coeffs,
                               decision_step)
from repro.fl.round import (local_sgd, make_sharded_round_update,
                            masked_aggregate, pack_participants,
                            sample_batches)
from repro.models.registry import make_model

# fold_in tags for the population side-channels (same idiom as the channel
# init's CHANNEL_INIT_TAG: side-channels of the round key leave the engines'
# 3-way (k_ch, k_sel, k_bat) split untouched)
POP_INIT_TAG = 0x7069   # "pi": the round-0 activity mask
POP_CHURN_TAG = 0x7063  # "pc": per-round arrival/departure uniforms
POP_FAIL_TAG = 0x7066   # "pf": per-round post-selection failure uniforms


@dataclasses.dataclass(frozen=True)
class PopulationConfig:
    """Markov churn + straggler scenario over the fixed max-N arena.

    The default is the degenerate scenario — everyone active forever,
    every delivery succeeds — under which every engine is bitwise-equal to
    its population-free self (the all-active contract).
    """

    p_join: float = 0.0      # P[inactive lane joins next round]
    p_leave: float = 0.0     # P[active device departs next round]
    p_fail: float = 0.0      # P[selected device fails to deliver]
    init_active: float = 1.0  # P[lane starts active] (1.0: everyone)

    def validate(self):
        for name in ("p_join", "p_leave", "p_fail", "init_active"):
            v = float(getattr(self, name))
            if not 0.0 <= v <= 1.0:
                raise ValueError(f"PopulationConfig.{name}={v} must be a "
                                 f"probability in [0, 1]")
        return self


def population_config(params) -> PopulationConfig:
    """((name, value), ...) | dict | PopulationConfig -> validated config."""
    if isinstance(params, PopulationConfig):
        return params.validate()
    return PopulationConfig(**dict(params)).validate()


def _ensure_one(mask: jax.Array, score: jax.Array) -> jax.Array:
    """Force the first-max-score lane on when ``mask`` is empty (the
    population-level mirror of the selection layer's ``guarantee_one``)."""
    none = ~jnp.any(mask)
    forced = jnp.zeros_like(mask).at[jnp.argmax(score)].set(True)
    return jnp.where(none, forced, mask)


def draw_churn_raw(key: jax.Array, n: int) -> jax.Array:
    """Per-round churn uniforms — a ``fold_in`` side-channel of the round
    key, drawn full-shape so the bits are mesh-invariant (the client-sharded
    engine hands each shard its slice, like ``CHANNEL_RAW``)."""
    return jax.random.uniform(jax.random.fold_in(key, POP_CHURN_TAG), (n,))


def draw_fail_raw(key: jax.Array, n: int) -> jax.Array:
    """Per-round straggler-failure uniforms (side-channel, full-shape)."""
    return jax.random.uniform(jax.random.fold_in(key, POP_FAIL_TAG), (n,))


def init_active_mask(key: jax.Array, n: int,
                     pcfg: PopulationConfig) -> jax.Array:
    """The round-0 (N,) activity mask. ``init_active=1.0`` gives all-True
    exactly (uniforms live in [0, 1))."""
    u = jax.random.uniform(jax.random.fold_in(key, POP_INIT_TAG), (n,))
    return _ensure_one(u < pcfg.init_active, u)


def churn_step(raw: jax.Array, active: jax.Array,
               pcfg: PopulationConfig) -> jax.Array:
    """One Markov arrival/departure step on pre-drawn uniforms.

    ``p_join = p_leave = 0`` reproduces ``active`` exactly (uniforms are
    ``>= 0`` and ``< 1``), which the all-active bitwise contract uses.
    """
    new = jnp.where(active, raw >= pcfg.p_leave, raw < pcfg.p_join)
    return _ensure_one(new, raw)


def failure_split(raw: jax.Array, sel: jax.Array, pcfg: PopulationConfig):
    """Split a selection into (delivered, failed) on pre-drawn uniforms.

    ``p_fail = 0`` makes ``delivered`` exactly ``sel``. Failed devices are
    the timeout model's stragglers: charged airtime and power upstream,
    invisible to the aggregation downstream.
    """
    failed = sel & (raw < pcfg.p_fail)
    return sel & ~failed, failed


def active_count(active: jax.Array) -> jax.Array:
    """Traced active-lane count (the ``n_active`` policy operand)."""
    return jnp.sum(active.astype(jnp.int32))


# --------------------------------------------------------------------------
# The population-aware round core (the masked twin of engine.make_round_core).
# --------------------------------------------------------------------------

def make_population_core(ds: FederatedDataset, sim, scfg: SchedulerConfig,
                         pcfg: PopulationConfig, decision=None):
    """The mask-threaded round body for the scan engine and the grid.

    Returns ``pop_core(channel_step, policy_step, acct, params, pol_state,
    (ch_state, active), key) -> (params, pol_state, (ch_state, active'),
    t_comm, power, n_sel)`` — the same shape contract as
    ``engine.make_round_core``'s product except the channel-state carry
    slot is the ``(ch_state, active)`` pair, so ``run_config_chunks`` and
    the whole history machinery drive it unchanged.

    Order of events per round: churn -> channel obs -> masked decision
    (selection + Eq. 9 charge on the post-churn mask) -> straggler split ->
    training on the delivered participants only.

    ``decision`` swaps the decision layer (default ``decision_step``);
    ``solver="pallas_fused"`` passes the megakernel drop-in, whose
    ``valid`` argument doubles as the activity mask — inside the kernel
    it masks q -> 0 pre-selection AND the expected-power summand, the
    same two uses the stitched masked policy makes of it. Failed lanes
    stay charged either way: Eq. 9 takes no failure input.
    """
    n = ds.n_clients
    m_cap = sim.m_cap
    spec = make_model(sim.model, ds, **dict(sim.model_params))
    from repro.fl.engine import resolve_wire_dtype
    wire = resolve_wire_dtype(sim.wire_dtype)
    if sim.client_shards:
        raise ValueError(
            "make_population_core builds the single-device-client round; "
            "client_shards needs fl/client_shard.py's population round "
            "(make_sim_round dispatches)")
    sharded_update = None
    if sim.participant_shards:
        sharded_update = make_sharded_round_update(
            spec.loss_fn, sim.gamma, sim.local_steps, n,
            sim.participant_shards, aggregation=sim.aggregation,
            wire_dtype=wire)
    if decision is None:
        decision = decision_step

    def pop_core(channel_step, policy_step, acct, params, pol_state, cst,
                 key):
        ch_state, active = cst
        k_ch, k_sel, k_bat = jax.random.split(key, 3)
        churn_raw = draw_churn_raw(key, n)
        fail_raw = draw_fail_raw(key, n)
        active = churn_step(churn_raw, active, pcfg)
        gains, ch_state = channel_obs(channel_step, k_ch, ch_state)
        n_act = active_count(active)
        # the policy layer owns the masking (q -> 0 on inactive lanes
        # BEFORE selection and the Eq. 9 charge; subset sizes clip into
        # n_active); decision_step's valid hook keeps inactive lanes out
        # of the power accounting exactly like the service's pad lanes
        masked_step = lambda k, g, st: policy_step(k, g, st, active, n_act)  # noqa: E731
        sel, q, p, t_comm, power, n_sel, pol_state = decision(
            masked_step, acct, k_sel, gains, pol_state, valid=active)
        # stragglers: selected-but-failed devices burned their TDMA slot
        # (t_comm and n_sel keep them) but deliver nothing downstream
        delivered, _failed = failure_split(fail_raw, sel, pcfg)
        sel_idx, sel_valid, _ = pack_participants(delivered, m_cap)
        q_sel = q[sel_idx]
        imgs, labs = sample_batches(k_bat, ds.client_images,
                                    ds.client_labels, sel_idx, m_cap,
                                    sim.local_steps, sim.batch)
        if sharded_update is not None:
            new_params = sharded_update(params, imgs, labs, sel_valid,
                                        q_sel)
        else:
            updated = jax.lax.map(
                lambda b: local_sgd(spec.loss_fn, params, b, sim.gamma,
                                    sim.local_steps), (imgs, labs))
            new_params = masked_aggregate(params, updated, sel_valid,
                                          q_sel, n, sim.aggregation, wire)
        return (new_params, pol_state, (ch_state, active), t_comm, power,
                n_sel)

    return pop_core


def make_population_round(ds: FederatedDataset, sim, scfg: SchedulerConfig,
                          ch, sigmas: jax.Array,
                          coeffs: DecisionCoeffs = None):
    """Bind :func:`make_population_core` to ``sim``'s channel + policy —
    the population twin of ``engine.make_sim_round``'s sequential path
    (``make_sim_round`` dispatches here when ``sim.population`` is set)."""
    from repro.fl.engine import resolve_fused_decision
    pcfg = population_config(sim.population)
    co = coeffs if coeffs is not None else decision_coeffs(scfg, ch)
    channel = make_channel(sim.channel, sigmas, ch,
                           **dict(sim.channel_params))
    policy_step = make_policy(sim.policy, scfg, ch, m_avg=sim.uniform_m,
                              coeffs=co.solve, **dict(sim.policy_params))
    pop_core = make_population_core(ds, sim, scfg, pcfg,
                                    decision=resolve_fused_decision(sim,
                                                                    scfg,
                                                                    co))

    def sim_round(params, pol_state, cst, key):
        return pop_core(channel.step, policy_step, co.acct, params,
                        pol_state, cst, key)

    return sim_round
