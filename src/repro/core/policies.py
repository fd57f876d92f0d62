"""Selection-policy registry: Algorithm 2 + five baselines, one interface.

Every policy is a step

    step(key, gains, state) -> (selected, q, P, state)

over a shared fixed-shape :class:`PolicyState` (``z``: Algorithm-2 virtual
power queues; ``aux``: per-client scratch — update-norm proxy or age; ``t``:
round counter), so any policy drops into the scan engine, the batched sweep,
and the shard_map scenario grid unchanged.

Registered policies (see ``docs/paper_map.md`` for the paper map):

* ``proposed`` — Algorithm 2: Lyapunov drift-plus-penalty solve (Theorem 2,
  Eqs. 16/17) + Bernoulli sampling + Eq. (9) queue update.
* ``uniform`` — the paper's Section-VI baseline: M-matched uniform selection
  with P_n = Pbar N / M'.
* ``greedy_channel`` — top-M instantaneous channels (Nishio & Yonetani
  [14]-style resource-greedy selection). Fast per round but BIASED: clients
  with persistently bad channels never participate, so with non-iid data the
  global model drifts (no 1/q correction exists because q = 0 for some
  clients — exactly the failure mode Theorem 1's non-zero-q condition rules
  out).
* ``proportional_gain`` — Bernoulli sampling with q proportional to the
  clipped gain (normalized to a target average M), q > 0 everywhere so the
  Algorithm-1 1/q correction still applies.
* ``update_aware`` — gradient-norm-weighted selection in the spirit of
  Amiri et al. (arXiv:2001.10402): clients accumulate local updates while
  unscheduled, and the scheduler favors large accumulated-update norms. The
  scheduling layer has no gradients, so ``aux`` carries the standard proxy —
  the norm estimate grows by one model-update unit per skipped round and
  resets on transmission.
* ``aoi_capped`` — age-of-information-capped selection (Yang et al.-style
  AoI scheduling): every client whose age exceeds ``max_age`` is forced in,
  remaining slots go to the best instantaneous channels. Deterministic given
  the gains, q degenerate in {0,1} like ``greedy_channel``.

All baselines use P_n = Pbar * N / M' like the paper's uniform baseline,
satisfying the average-power constraint by construction.
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.channel import ChannelConfig
from repro.core.fences import pin
from repro.core.scheduler import (SchedulerConfig, greedy_coeffs,
                                  greedy_decide, sample_selection,
                                  solve_round, solve_round_coeffs,
                                  uniform_coeffs, uniform_draw_m,
                                  update_queues_z)


def greedy_channel(key, gains: jax.Array, m: int, ch: ChannelConfig):
    """Select the top-m channels. Returns (selected, q, P).

    q is reported as the *realized* indicator (there is no valid inverse-
    propensity weight for never-selected clients; aggregation must fall
    back to plain averaging over participants — biased under non-iid).
    The math lives in :func:`repro.core.scheduler.greedy_decide`, shared
    with the scheduler service's coefficient-operand form."""
    n = gains.shape[0]
    return greedy_decide(gains, greedy_coeffs(n, float(m), ch))


def proportional_gain(key, gains: jax.Array, m_avg: float,
                      ch: ChannelConfig, q_floor: float = 1e-3):
    """Bernoulli selection with q_n proportional to |h_n|^2, scaled so
    E[sum q] = m_avg, floored at q_floor (keeps Theorem 1 applicable)."""
    n = gains.shape[0]
    q = gains / jnp.sum(gains) * m_avg
    q = jnp.clip(q, q_floor, 1.0)
    sel = jax.random.uniform(key, (n,)) < q
    m_draw = jnp.maximum(jnp.sum(sel), 1)
    p = jnp.full((n,), ch.p_bar * n / m_draw, jnp.float32)
    return sel, q, p


# --------------------------------------------------------------------------
# Unified policy interface.
# --------------------------------------------------------------------------

class PolicyState(NamedTuple):
    """Fixed-shape cross-policy state (same pytree for every policy, so a
    grid can carry one state and switch policies per config)."""

    z: jax.Array    # (N,) f32: Algorithm-2 virtual power queues (Eq. 9)
    aux: jax.Array  # (N,) f32: policy scratch (update-norm proxy / AoI age)
    t: jax.Array    # ()   i32: round counter


PolicyStep = Callable[[jax.Array, jax.Array, PolicyState],
                      Tuple[jax.Array, jax.Array, jax.Array, PolicyState]]

# Dynamic populations (repro.fl.population): every step also accepts two
# trailing operands ``(active, n_active)`` — a (N,) bool activity mask over
# the fixed arena plus its traced count. ``None`` (the default everywhere)
# is a PYTHON-level branch, so legacy callers trace the exact historic
# program, bit for bit. With a mask, each policy masks q to 0 on inactive
# lanes BEFORE selection and before the Eq. 9 queue update (Z is charged
# the expected power P*q of what the scheduler could actually have
# selected), and clips its subset size into the active count so score
# thresholds can never tie into inactive sentinel lanes. When the mask is
# all-True every masking select is value-preserving per lane, which is what
# the all-active bitwise contract with the legacy engines rests on.


def _aux0_zeros(n: int) -> jax.Array:
    return jnp.zeros((n,), jnp.float32)


def _aux0_ones(n: int) -> jax.Array:
    return jnp.ones((n,), jnp.float32)


def _make_proposed(scfg: SchedulerConfig, ch: ChannelConfig, m_avg,
                   coeffs=None) -> PolicyStep:
    """Algorithm 2. ``coeffs`` (a SolveCoeffs pytree, typically of traced
    scalars passed through the caller's jit boundary) switches the solve
    and the Eq. 9 queue update onto coefficient operands — the engines and
    the scheduler service both use this form so their decisions agree bit
    for bit (the operand contract, repro/core/scheduler.py)."""
    if coeffs is not None:
        solve = lambda gains, z: solve_round_coeffs(gains, z, coeffs)  # noqa: E731
    else:
        solve = lambda gains, z: solve_round(gains, z, scfg, ch)  # noqa: E731
    pbar_src = ch if coeffs is None else coeffs

    def step(key, gains, st: PolicyState, active=None, n_active=None):
        q, p = solve(gains, st.z)
        if active is not None:
            q = jnp.where(active, q, 0.0)
        sel = sample_selection(key, q, scfg.guarantee_one)
        z = update_queues_z(st.z, q, p, pbar_src)
        return sel, q, p, PolicyState(z, st.aux, st.t + 1)

    return step


def _make_uniform(scfg, ch, m_avg) -> PolicyStep:
    from repro.core.scheduler import uniform_selection

    def step(key, gains, st: PolicyState, active=None, n_active=None):
        if active is None:
            sel, q, p = uniform_selection(key, scfg.n_clients, m_avg, ch)
        else:
            # uniform_decide, mask-hardened: M' clips into the ACTIVE
            # count (see uniform_draw_m) and inactive scores sink to -1,
            # below every live score in [0, 1)
            c = uniform_coeffs(scfg.n_clients, m_avg, ch)
            k1, k2, _ = jax.random.split(key, 3)
            take = jax.random.uniform(k1)
            scores = jnp.where(active,
                               jax.random.uniform(k2, (scfg.n_clients,)),
                               -1.0)
            take_hi = take < (c.m_avg - jnp.floor(c.m_avg))
            m = uniform_draw_m(take_hi, c.m_avg, c.n, n_active=n_active)
            thresh = -jnp.sort(-scores)[m - 1]
            sel = scores >= thresh
            q = jnp.where(active,
                          jnp.full((scfg.n_clients,), c.q_val, jnp.float32),
                          0.0)
            p = jnp.full((scfg.n_clients,),
                         (c.pn / jnp.maximum(m, 1)).astype(jnp.float32),
                         jnp.float32)
        return sel, q, p, PolicyState(st.z, st.aux, st.t + 1)

    return step


def _make_greedy(scfg, ch, m_avg) -> PolicyStep:
    m = max(1, int(round(m_avg)))

    def step(key, gains, st: PolicyState, active=None, n_active=None):
        if active is None:
            sel, q, p = greedy_channel(key, gains, m, ch)
        else:
            c = greedy_coeffs(gains.shape[0], float(m), ch)
            m_eff = jnp.clip(c.m, 1, jnp.maximum(n_active, 1))
            score = jnp.where(active, gains, -jnp.inf)
            thresh = -jnp.sort(-score)[m_eff - 1]
            sel = score >= thresh
            q = sel.astype(jnp.float32)
            p = jnp.full_like(gains, c.pn / jnp.maximum(c.m, 1))
        return sel, q, p, PolicyState(st.z, st.aux, st.t + 1)

    return step


def _make_proportional(scfg, ch, m_avg,
                       q_floor: float = 1e-3) -> PolicyStep:
    def step(key, gains, st: PolicyState, active=None, n_active=None):
        if active is None:
            sel, q, p = proportional_gain(key, gains, m_avg, ch, q_floor)
        else:
            n = gains.shape[0]
            g = jnp.where(active, gains, 0.0)
            q = g / jnp.sum(g) * m_avg
            q = jnp.where(active, jnp.clip(q, q_floor, 1.0), 0.0)
            sel = jax.random.uniform(key, (n,)) < q
            m_draw = jnp.maximum(jnp.sum(sel), 1)
            p = jnp.full((n,), ch.p_bar * n / m_draw, jnp.float32)
        return sel, q, p, PolicyState(st.z, st.aux, st.t + 1)

    return step


def _make_update_aware(scfg, ch, m_avg,
                       q_floor: float = 1e-3) -> PolicyStep:
    n = scfg.n_clients

    def step(key, gains, st: PolicyState, active=None, n_active=None):
        norms = st.aux  # accumulated-update-norm proxy, grows while skipped
        norms_eff = norms if active is None else jnp.where(active, norms,
                                                           0.0)
        q = norms_eff / jnp.maximum(jnp.sum(norms_eff), 1e-12) * m_avg
        q = jnp.clip(q, q_floor, 1.0)
        if active is not None:
            q = jnp.where(active, q, 0.0)
        sel = jax.random.uniform(key, (n,)) < q
        m_draw = jnp.maximum(jnp.sum(sel), 1)
        p = jnp.full((n,), ch.p_bar * n / m_draw, jnp.float32)
        aux = jnp.where(sel, 1.0, norms + 1.0)
        if active is not None:
            # departed clients keep their proxy frozen: no local training
            # happens while away, so the estimate neither grows nor resets
            aux = jnp.where(active, aux, norms)
        return sel, q, p, PolicyState(st.z, aux, st.t + 1)

    return step


def _make_aoi_capped(scfg, ch, m_avg,
                     max_age: Optional[int] = None) -> PolicyStep:
    n = scfg.n_clients
    m = max(1, int(round(m_avg)))
    if max_age is None:
        # default cap: twice the uniform-selection revisit time N/M
        max_age = max(2, int(round(2.0 * n / m)))
    cap = jnp.float32(max_age)
    _FORCE = jnp.float32(1e30)  # above any clipped gain

    def step(key, gains, st: PolicyState, active=None, n_active=None):
        age = st.aux
        forced = age >= cap
        if active is not None:
            forced = forced & active
        # forced clients all share the same top score; the `| forced` union
        # below is what guarantees every one of them is selected even when
        # there are more than m of them
        score = jnp.where(forced, _FORCE, gains)
        if active is None:
            m_eff = m
        else:
            score = jnp.where(active, score, -jnp.inf)
            m_eff = jnp.clip(jnp.int32(m), 1, jnp.maximum(n_active, 1))
        thresh = -jnp.sort(-score)[m_eff - 1]
        sel = (score >= thresh) | forced
        q = sel.astype(jnp.float32)  # degenerate, like greedy_channel
        m_draw = jnp.maximum(jnp.sum(sel), 1)
        p = jnp.full((n,), ch.p_bar * n / m_draw, jnp.float32)
        # inactive clients keep aging: their information keeps staling
        # while away, so a rejoining client is (correctly) force-eligible
        aux = jnp.where(sel, 0.0, age + 1.0)
        return sel, q, p, PolicyState(st.z, aux, st.t + 1)

    return step


# name -> (builder, aux-initializer, needs matched-M?)
POLICIES = {
    "proposed": (_make_proposed, _aux0_zeros, False),
    "uniform": (_make_uniform, _aux0_zeros, True),
    "greedy_channel": (_make_greedy, _aux0_zeros, True),
    "proportional_gain": (_make_proportional, _aux0_zeros, True),
    "update_aware": (_make_update_aware, _aux0_ones, True),
    "aoi_capped": (_make_aoi_capped, _aux0_zeros, True),
}


# --------------------------------------------------------------------------
# PRNG draw plans: the randomness each policy step consumes, split out of the
# step so the client-sharded engine (repro.fl.client_shard) can draw it
# full-shape OUTSIDE its shard_map — the same traced draw as the sequential
# step, so the bits per client lane cannot depend on the mesh size — and
# hand each shard its slice. Each ``draw(key, n) -> raw`` consumes ``key``
# exactly as the sequential step does (same splits, same call order), which
# is what the mesh-1 bitwise parity contract rests on.
# --------------------------------------------------------------------------

def _draw_proposed(key, n):
    # sample_selection draws uniform(key, q.shape) with the step key directly
    return jax.random.uniform(key, (n,))


def draw_selection_uniform(key, n):
    """The ``proposed`` policy's selection uniforms, exactly as
    ``sample_selection`` draws them from the step key. Public alias for
    raw-carrying callers — the fused decision path
    (``fl/decision.py::make_fused_decision``) and the client-sharded
    engine — so a pre-drawn ``u`` can never drift from the stitched
    policy's in-step draw (same key, same shape, same dtype)."""
    return _draw_proposed(key, n)


def _draw_uniform(key, n):
    # uniform_selection: k1 (ceil-branch Bernoulli), k2 (scores), k3 unused
    k1, k2, k3 = jax.random.split(key, 3)
    del k3
    return {"take": jax.random.uniform(k1),
            "scores": jax.random.uniform(k2, (n,))}


def _draw_greedy(key, n):
    return ()  # deterministic given the gains


# Policies with a client-sharded implementation (see repro.fl.client_shard;
# the others need global normalizations — sum of aux norms, global age
# forcing — that have no exact sharded form yet).
POLICY_DRAWS = {
    "proposed": _draw_proposed,
    "uniform": _draw_uniform,
    "greedy_channel": _draw_greedy,
}

# Stable ids for lax.switch dispatch and sweep flags; insertion order above
# (the first two match the engine's historical {proposed: 0, uniform: 1}).
POLICY_IDS = {name: i for i, name in enumerate(POLICIES)}


def init_policy_state(name: str, n_clients: int) -> PolicyState:
    """Fresh per-policy state (zero queues; aux per the policy's semantics)."""
    _, aux0, _ = _lookup(name)
    return PolicyState(z=jnp.zeros((n_clients,), jnp.float32),
                       aux=aux0(n_clients), t=jnp.zeros((), jnp.int32))


def policy_aux_init(name: str, n_clients: int) -> jax.Array:
    """Just the aux initializer — grids stack these into a (P, N) table."""
    return _lookup(name)[1](n_clients)


def _lookup(name: str):
    if name not in POLICIES:
        raise ValueError(f"unknown policy {name!r} "
                         f"(registered: {sorted(POLICIES)})")
    return POLICIES[name]


def make_policy(name: str, scfg: SchedulerConfig, ch: ChannelConfig, *,
                m_avg: float = 0.0, coeffs=None, **params) -> PolicyStep:
    """Bind a registered policy to its configuration.

    ``m_avg`` is the matched average participation level M (Section VI);
    required (> 0) by every baseline, ignored by ``proposed``. ``coeffs``
    (a SolveCoeffs of runtime operands) switches ``proposed`` onto the
    coefficient-driven solve the engines and the scheduler service
    share — the baselines are exact-selection policies
    (comparisons, sorts, fills, one division) whose constants are
    bit-stable either way, so they ignore it. Extra ``params`` are
    policy-specific (``q_floor``, ``max_age``).
    """
    builder, _, needs_m = _lookup(name)
    if needs_m and not m_avg > 0.0:
        raise ValueError(f"policy {name!r} needs m_avg > 0 (matched average "
                         f"participation), got {m_avg!r}")
    if name == "proposed" and coeffs is not None:
        params = dict(params, coeffs=coeffs)
    return _fence(builder(scfg, ch, m_avg, **params))


def _fence(step: PolicyStep) -> PolicyStep:
    """Pin a policy step's inputs and outputs into a closed fusion region.

    The scenario grid runs a policy step inside a much larger program than
    a single-config run does, and XLA fuses/hoists across the step boundary
    differently per surrounding program — worth ~1 ulp of f32 drift per
    round. Fencing the step in every context (make_policy is the single
    entry point) keeps the interior graph identical everywhere, which the
    grid's bitwise-parity contract with run_simulation_scan depends on
    (tests/test_grid.py).
    """
    def fenced(key, gains, st, *mask):
        # ``mask`` is the optional (active, n_active) operand pair of the
        # dynamic-population engines; when absent (every legacy caller)
        # this traces the exact historic program
        key, gains, st = pin((key, gains, st))
        if mask:
            mask = pin(mask)
        return pin(step(key, gains, st, *mask))

    return fenced


# Public alias: the scheduler service fences its coefficient-driven policy
# steps with the exact same discipline (same pins, same pytree shape), which
# the bitwise-parity contract with the engines requires.
fence_step = _fence
