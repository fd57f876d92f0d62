"""Algorithm 2: Lyapunov drift-plus-penalty client scheduling (the paper's core).

Per round t and per client n, the Min Drift-Plus-Penalty problem (Eq. 15)

    min_{q, P}  V * ( 1/(N q) + lam * ell * q / (B log2(1 + |h|^2 P / N0)) )
                + Z * (P q - Pbar)
    s.t. 0 <= P <= Pmax,  q in (0, 1]

separates over clients and has a closed-form interior solution (Theorem 2):

    A      = V lam ell |h|^2 (ln 2)^2 / (N0 B Z)
    P_opt  = N0/|h|^2 * ( (A/4) * W0(sqrt(A/4))^{-2} - 1 )            (Eq. 16)
    q_opt  = ( lam ell N / (B log2(1+|h|^2 P_opt/N0)) + (N/V) Z P_opt )^{-1/2}
                                                                       (Eq. 17)

with the boundary fallback P = Pmax, q = min{Eq.17(Pmax), 1}. Instead of the
paper's Hessian determinant test we evaluate the per-client objective at both
candidates and keep the smaller — equivalent selection of the minimizer, and
branch-free (jit/vmap friendly).

Virtual power queues follow Eq. (9): Z(t+1) = max(Z + P q - Pbar, 0).

Only instantaneous CSI (|h_n(t)|^2) is consumed — no channel statistics — and
the per-client solve is local, mirroring the paper's distributed computation.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.channel import ChannelConfig, channel_rate
from repro.core.lambertw import lambertw0

_LN2 = 0.6931471805599453
_EPS = 1e-12


@dataclasses.dataclass(frozen=True)
class SchedulerConfig:
    """Hyper-parameters of Algorithm 2."""

    n_clients: int
    model_bits: float                   # ell: bits per model transmission
    lam: float = 10.0                   # lambda: comm-time vs bound trade-off
    V: float = 1000.0                   # Lyapunov penalty weight
    q_floor: float = 1e-5               # numerical floor to keep q in (0,1]
    guarantee_one: bool = True          # force >=1 participant per round (paper VI)


class SchedulerState(NamedTuple):
    """Carried across rounds; Z are the per-client virtual power queues."""

    z: jax.Array         # (N,) virtual queues
    t: jax.Array         # round counter (int32)


def init_state(cfg: SchedulerConfig) -> SchedulerState:
    return SchedulerState(z=jnp.zeros((cfg.n_clients,), jnp.float32),
                          t=jnp.zeros((), jnp.int32))


# --------------------------------------------------------------------------
# Per-client closed-form solve.
#
# The solve is written over a :class:`SolveCoeffs` bundle of scalar
# operands rather than over the raw (SchedulerConfig, ChannelConfig)
# fields. Two deployment modes share this one implementation:
#
# * the simulation engines bake the coefficients as CONSTANTS (host-folded
#   in float64 from the Python-float configs, rounded once to float32 —
#   exactly the constants a Python-float trace would bake);
# * the multi-tenant scheduler service (repro.service) feeds them as
#   TRACED per-tenant scalars, vmapped over a bucket of tenants.
#
# THE OPERAND CONTRACT: for two programs to produce bitwise-identical
# q/P, the coefficients must have the same *provenance* in both — either
# both baked as literals, or both passed as runtime operands through a
# jit boundary. Mixing the two is NOT bit-stable: XLA/LLVM specialize a
# kernel around literal constants (eliding x*1.0, folding bw into log2's
# internal 1/ln2, forming FMAs the runtime-operand version cannot), which
# drifts results by ~1 ulp — and ``optimization_barrier`` does NOT help,
# because the barriers are consumed before the emitter makes those
# choices (verified empirically; see tests/test_service.py's contract
# suite). The engines therefore pass their coefficient bundle through
# their top-level jit boundary as a runtime argument, matching the
# service's traced per-tenant scalars. Runtime-operand programs ARE
# bit-stable across array shapes, batching (vmap), and padding — the
# property the whole service contract rests on (0 mismatches in a
# 200-config stress across shapes 7..1537, buckets, and batch sizes).
# --------------------------------------------------------------------------

class SolveCoeffs(NamedTuple):
    """Scalar operands of the Theorem-2 solve (one value per tenant/config).

    Products are folded on the host in float64 and rounded once to f32 —
    the same constants a jit trace of Python-float configs produces — so a
    coefficient-driven solve and a config-driven solve are bitwise-equal.
    Build with :func:`solve_coeffs`; stack leaves to vmap over tenants.
    """

    a_coef: jax.Array    # V lam ell ln2 / (N0 B): Eq. 16 argument scale
    n0: jax.Array        # N0
    bw: jax.Array        # B
    p_max: jax.Array     # Pmax
    lle_n: jax.Array     # lam ell N      (Eq. 17 rate term)
    n_over_v: jax.Array  # N / V          (Eq. 17 queue term)
    q_floor: jax.Array   # numerical floor keeping q in (0, 1]
    n: jax.Array         # N (as f32)
    lle: jax.Array       # lam ell        (objective comm term)
    v: jax.Array         # V
    p_bar: jax.Array     # Pbar


def solve_coeffs(cfg: SchedulerConfig, ch: ChannelConfig) -> SolveCoeffs:
    """Fold (cfg, ch) into the solve's scalar operands (host, f64 -> f32)."""
    d = np.float64
    f = np.float32
    return SolveCoeffs(
        a_coef=f(d(cfg.V) * d(cfg.lam) * d(cfg.model_bits) * d(_LN2)
                 / (d(ch.noise_power) * d(ch.bandwidth_hz))),
        n0=f(ch.noise_power), bw=f(ch.bandwidth_hz), p_max=f(ch.p_max),
        lle_n=f(d(cfg.lam) * d(cfg.model_bits) * d(cfg.n_clients)),
        n_over_v=f(d(cfg.n_clients) / d(cfg.V)), q_floor=f(cfg.q_floor),
        n=f(cfg.n_clients), lle=f(d(cfg.lam) * d(cfg.model_bits)),
        v=f(cfg.V), p_bar=f(ch.p_bar))


def coeff_rate(gains, power, c) -> jax.Array:
    """:func:`~repro.core.channel.channel_rate` over coefficient operands.

    ``c`` needs ``bw`` / ``n0`` fields (a :class:`SolveCoeffs` or the
    decision layer's account bundle) with the operand provenance described
    in the module comment above.
    """
    return c.bw * jnp.log2(1.0 + gains * power / c.n0)


def _objective_c(q, p, gains, z, c: SolveCoeffs):
    """Per-client drift-plus-penalty objective f(q, P) of Eq. (15)."""
    rate = coeff_rate(gains, p, c)
    y0 = 1.0 / (c.n * q) + c.lle * q / jnp.maximum(rate, _EPS)
    return c.v * y0 + z * (p * q - c.p_bar)


def _q_eq17_c(p, gains, z, c: SolveCoeffs):
    """Eq. (17) for a given power; clipped into (q_floor, 1]."""
    rate = coeff_rate(gains, p, c)
    inv_sq = (c.lle_n / jnp.maximum(rate, _EPS)
              + c.n_over_v * z * p)
    q = jax.lax.rsqrt(jnp.maximum(inv_sq, _EPS))
    return jnp.clip(q, c.q_floor, 1.0)


def solve_candidates_coeffs(gains: jax.Array, z: jax.Array, c: SolveCoeffs):
    """:func:`solve_candidates` over a (possibly traced) coefficient bundle."""
    gains = gains.astype(jnp.float32)
    z = z.astype(jnp.float32)
    zs = jnp.maximum(z, _EPS)  # Z=0 -> A=inf -> boundary branch wins anyway

    # Interior candidate (Eq. 16). NOTE: the paper prints
    # A = V lam ell |h|^2 (log 2)^2 / (N0 B Z); re-deriving d f / d P = 0
    # gives x (ln x)^2 = V lam ell |h|^2 ln(2) / (N0 B Z) — one power of
    # ln 2, not two. The grid-search property test
    # (tests/test_scheduler.py::test_closed_form_beats_grid) confirms the
    # corrected constant; the paper's version is ~0.5% suboptimal in f.
    a = c.a_coef * gains / zs
    w = lambertw0(jnp.sqrt(a / 4.0))
    p_int = c.n0 / gains * (a / (4.0 * jnp.maximum(w * w, _EPS)) - 1.0)
    p_int = jnp.clip(p_int, 0.0, c.p_max)
    q_int = _q_eq17_c(p_int, gains, z, c)

    # Boundary candidate: P = Pmax (also Algorithm 2's t=0 branch when Z=0).
    p_bnd = jnp.broadcast_to(c.p_max, gains.shape)
    q_bnd = _q_eq17_c(p_bnd, gains, z, c)

    # Keep the smaller objective (replaces the Hessian determinant test).
    f_int = _objective_c(q_int, p_int, gains, z, c)
    f_bnd = _objective_c(q_bnd, p_bnd, gains, z, c)
    use_int = jnp.isfinite(f_int) & (f_int <= f_bnd)
    return q_int, p_int, q_bnd, p_bnd, use_int


def solve_round_coeffs(gains: jax.Array, z: jax.Array,
                       c: SolveCoeffs) -> Tuple[jax.Array, jax.Array]:
    """Theorem-2 solve from a coefficient bundle: -> (q, P), each (N,).

    The service's per-tenant entry point; bitwise-equal to
    :func:`solve_round` on the same (cfg, ch) by construction.
    """
    q_int, p_int, q_bnd, p_bnd, use_int = solve_candidates_coeffs(gains, z,
                                                                  c)
    q = jnp.where(use_int, q_int, q_bnd)
    p = jnp.where(use_int, p_int, p_bnd)
    return q, p


def _objective(q, p, gains, z, cfg: SchedulerConfig, ch: ChannelConfig):
    """Config-signature wrapper of :func:`_objective_c` (kept for tests)."""
    return _objective_c(q, p, gains, z, solve_coeffs(cfg, ch))


def _q_eq17(p, gains, z, cfg: SchedulerConfig, ch: ChannelConfig):
    """Config-signature wrapper of :func:`_q_eq17_c`."""
    return _q_eq17_c(p, gains, z, solve_coeffs(cfg, ch))


def solve_candidates(gains: jax.Array, z: jax.Array, cfg: SchedulerConfig,
                     ch: ChannelConfig):
    """Both Theorem-2 candidates plus the branch-free keep decision.

    Returns ``(q_int, p_int, q_bnd, p_bnd, use_int)``: the interior
    (Eq. 16/17) and boundary (P = Pmax) candidates, and the boolean mask of
    clients where the interior candidate's objective wins. Exposed so the
    property tests can assert the kept candidate never loses to the
    discarded one (tests/test_scheduler.py); :func:`solve_round` is the
    thin selection on top.
    """
    return solve_candidates_coeffs(gains, z, solve_coeffs(cfg, ch))


def solve_round(gains: jax.Array, z: jax.Array, cfg: SchedulerConfig,
                ch: ChannelConfig) -> Tuple[jax.Array, jax.Array]:
    """Vectorized Theorem-2 solve: gains, z of shape (N,) -> (q, P) each (N,).

    Pure jnp. Internally the configs are folded to a :class:`SolveCoeffs`
    constant bundle, so this is literally :func:`solve_round_coeffs` with
    baked coefficients — the service's bitwise contract rests on that.
    """
    return solve_round_coeffs(gains, z, solve_coeffs(cfg, ch))


def update_queues_z(z: jax.Array, q: jax.Array, p: jax.Array,
                    ch) -> jax.Array:
    """Eq. (9) on the bare queue array: max(Z + P q - Pbar, 0).

    The single home of the queue dynamics — the SchedulerState form below
    and the policy registry's PolicyState form both delegate here. ``ch``
    only needs a ``p_bar`` field (a ChannelConfig, or a coefficient bundle
    so the engines and the service share operand provenance — see the
    module comment).
    """
    return jnp.maximum(z + p * q - ch.p_bar, 0.0)


def update_queues(state: SchedulerState, q: jax.Array, p: jax.Array,
                  ch: ChannelConfig) -> SchedulerState:
    """Eq. (9): Z(t+1) = max(Z + P q - Pbar, 0)."""
    return SchedulerState(z=update_queues_z(state.z, q, p, ch),
                          t=state.t + 1)


def selection_from_uniform(u: jax.Array, q: jax.Array,
                           guarantee_one: bool = True) -> jax.Array:
    """:func:`sample_selection` on pre-drawn uniforms: I_n = [u_n < q_n].

    Split out so the client-sharded engine can draw ``u`` full-shape outside
    its shard_map (mesh-invariant bits) and apply the comparison per shard;
    ``sample_selection`` composes the two, bit-for-bit the historic draw.
    """
    sel = u < q
    if guarantee_one:
        none = ~jnp.any(sel)
        forced = jnp.zeros_like(sel).at[jnp.argmax(q)].set(True)
        sel = jnp.where(none, forced, sel)
    return sel


def sample_selection(key: jax.Array, q: jax.Array,
                     guarantee_one: bool = True) -> jax.Array:
    """Draw the participation indicators I_n ~ Bernoulli(q_n), independently.

    If nothing was drawn and ``guarantee_one``, the client with the largest q
    is selected (paper Section VI's fallback).
    """
    return selection_from_uniform(jax.random.uniform(key, q.shape), q,
                                  guarantee_one)


def schedule_step(key: jax.Array, gains: jax.Array, state: SchedulerState,
                  cfg: SchedulerConfig, ch: ChannelConfig):
    """One full Algorithm-2 round: solve -> sample -> queue update.

    Returns (selected mask, q, P, new_state). jit-able; vmapped internally
    over all clients via the vectorized closed form.
    """
    q, p = solve_round(gains, state.z, cfg, ch)
    sel = sample_selection(key, q, cfg.guarantee_one)
    new_state = update_queues(state, q, p, ch)
    return sel, q, p, new_state


# --------------------------------------------------------------------------
# Baselines.
# --------------------------------------------------------------------------

def uniform_draw_m(take_hi: jax.Array, m_avg: float, n_clients: int,
                   n_active=None) -> jax.Array:
    """The uniform baseline's per-round subset size M' — floor(M) or
    ceil(M) (``take_hi`` is the pre-drawn Bernoulli for the ceil branch),
    **clipped into [1, N]**. The clip is the hardening for degenerate
    matched-M values: M <= 0 used to reach the score sort as m = 0-or-1
    only via a one-sided maximum, and M > N silently indexed the sort out
    of range (undefined under jit) — both now saturate instead.

    Under an activity mask (dynamic populations, ``repro.fl.population``)
    pass the traced active count as ``n_active``: the clip then saturates
    at max(n_active, 1) instead of N, so M' can never tie the score-sort
    threshold into inactive (sentinel-scored) lanes — the same bug class
    the greedy baseline's m > N clip fixed.
    """
    m_lo = jnp.floor(m_avg).astype(jnp.int32)
    m = jnp.where(take_hi, m_lo + 1, m_lo)
    hi = n_clients if n_active is None else jnp.maximum(n_active, 1)
    return jnp.clip(m, 1, hi)


class UniformCoeffs(NamedTuple):
    """Scalar operands of the M-matched uniform baseline (exact ops only,
    so constant- and operand-provenance runs agree bit for bit)."""

    m_avg: jax.Array   # matched average participation M (f32)
    q_val: jax.Array   # clip(M / N, 0, 1): the reported q
    pn: jax.Array      # Pbar * N: numerator of P = Pbar N / M'
    n: jax.Array       # N (i32: clips M' into [1, N])


class GreedyCoeffs(NamedTuple):
    """Scalar operands of the greedy top-M channel baseline."""

    m: jax.Array       # M (i32)
    pn: jax.Array      # Pbar * N


def uniform_coeffs(n_clients: int, m_avg: float,
                   ch: ChannelConfig) -> UniformCoeffs:
    """Host-folded operands of :func:`uniform_decide` (f64 folds, f32)."""
    d, f = np.float64, np.float32
    return UniformCoeffs(
        m_avg=f(m_avg),
        q_val=np.clip(f(d(m_avg) / n_clients), f(0.0), f(1.0)),
        pn=f(d(ch.p_bar) * n_clients), n=np.int32(n_clients))


def greedy_coeffs(n_clients: int, m_avg: float,
                  ch: ChannelConfig) -> GreedyCoeffs:
    """Host-folded operands of :func:`greedy_decide`."""
    return GreedyCoeffs(m=np.int32(max(1, int(round(m_avg)))),
                        pn=np.float32(np.float64(ch.p_bar) * n_clients))


def uniform_decide(raw, c: UniformCoeffs):
    """The uniform baseline's decision on pre-drawn raws: the single home
    of its math, shared by :func:`uniform_selection` (engine, baked
    coefficients) and the scheduler service (traced per-tenant
    coefficients). ``raw`` = {"take": (), "scores": (N',)} — N' may exceed
    c.n when the service pads the client axis; pad scores must be < 0.
    """
    take_hi = raw["take"] < (c.m_avg - jnp.floor(c.m_avg))
    m = uniform_draw_m(take_hi, c.m_avg, c.n)
    thresh = -jnp.sort(-raw["scores"])[m - 1]
    sel = raw["scores"] >= thresh
    # q/p are f32 REGARDLESS of the scores dtype: under JAX_ENABLE_X64 the
    # engines' raw uniforms draw as f64, and q/p must stay the f32 the
    # whole accounting/selection chain (and the x64 CI leg) is pinned to
    shape = raw["scores"].shape
    q = jnp.full(shape, c.q_val, jnp.float32)
    p = jnp.full(shape, (c.pn / jnp.maximum(m, 1)).astype(jnp.float32),
                 jnp.float32)
    return sel, q, p


def greedy_decide(gains: jax.Array, c: GreedyCoeffs):
    """Top-M instantaneous channels on given gains — the single home of
    the greedy baseline's math (see :func:`uniform_decide`). Pad gains
    must be below every real (clipped-positive) gain; q is the realized
    indicator (no valid inverse-propensity weight exists — see
    ``repro.core.policies.greedy_channel``)."""
    thresh = -jnp.sort(-gains)[c.m - 1]
    sel = gains >= thresh
    q = sel.astype(jnp.float32)
    p = jnp.full_like(gains, c.pn / jnp.maximum(c.m, 1))
    return sel, q, p


def uniform_selection(key: jax.Array, n_clients: int, m_avg: float,
                      ch: ChannelConfig):
    """FedAvg's uniform policy, strengthened as in the paper's Section VI.

    Selects floor(M) or ceil(M) clients uniformly at random (probability set
    so the mean is M, M clipped into [1, N] — see :func:`uniform_draw_m`),
    and allocates P_n = Pbar * N / M' to satisfy the average power
    constraint by design. Returns (selected, q, P). Score ties at the
    selection threshold keep every tied client (selection is by value, so
    the drawn subset can exceed M' only on exact f32 score collisions).

    Draw + :func:`uniform_decide` — the PRNG consumption here is what
    ``POLICY_DRAWS["uniform"]`` replicates for raw-carrying callers.
    """
    k1, k2, k3 = jax.random.split(key, 3)
    raw = {"take": jax.random.uniform(k1),
           "scores": jax.random.uniform(k2, (n_clients,))}
    del k3
    return uniform_decide(raw, uniform_coeffs(n_clients, m_avg, ch))


def estimate_avg_selected(key: jax.Array, sigmas: jax.Array, cfg: SchedulerConfig,
                          ch: ChannelConfig, rounds: int = 500,
                          channel=None) -> jax.Array:
    """Monte-Carlo estimate of M = E[sum_n q_n] under Algorithm 2.

    Used to match the uniform baseline's participation level (Section VI).
    Runs the real queue dynamics so the estimate reflects steady state.
    ``channel`` is an optional :class:`~repro.core.channel.ChannelModel`
    whose fading law the estimate should reflect (default: the paper's
    i.i.d. Rayleigh draws) — matching against the wrong gain distribution
    would silently skew every "M-matched" baseline comparison.
    """
    from repro.core.channel import draw_gains  # local import to avoid cycle

    def body(carry, k):
        st, ch_state = carry
        if channel is None:
            gains = draw_gains(k, sigmas, ch)
        else:
            gains, ch_state = channel.step(k, ch_state)
        q, p = solve_round(gains, st.z, cfg, ch)
        st = update_queues(st, q, p, ch)
        return (st, ch_state), jnp.sum(q)

    ch_state0 = (jnp.zeros((0,), jnp.float32) if channel is None
                 else channel.init(jax.random.fold_in(key, 1)))
    keys = jax.random.split(key, rounds)
    _, sums = jax.lax.scan(body, (init_state(cfg), ch_state0), keys)
    # Discard burn-in (first 20%) — queues start at 0.
    burn = rounds // 5
    return jnp.mean(sums[burn:])


def y0(q: jax.Array, p: jax.Array, gains: jax.Array, cfg: SchedulerConfig,
       ch: ChannelConfig) -> jax.Array:
    """The scheduling objective y0(t) of Eq. (8) — diagnostics/benchmarks."""
    rate = channel_rate(gains, p, ch)
    return jnp.sum(1.0 / (cfg.n_clients * jnp.maximum(q, _EPS))
                   + cfg.lam * cfg.model_bits * q / jnp.maximum(rate, _EPS))
