"""The shared per-round scheduling decision step: obs -> solve -> select ->
Z-update -> account.

Until this module, the pipeline existed three times: inlined in the scan
engine's round core (``fl/engine.py``), inlined in the client-sharded
scheduling runner (``fl/client_shard.py``), and re-derived by the
multi-tenant service. It is THE thing the paper deploys — everything else
(model training, eval, history) is simulation harness — so the online
scheduler service (``repro.service``) serves exactly this function, and
the binding correctness contract is that a served decision is
bitwise-equal to the decision the simulation engine would have taken
(tests/test_service.py).

Four pieces:

* :class:`DecisionCoeffs` — the decision layer's scalar operands (the
  Theorem-2 :class:`~repro.core.scheduler.SolveCoeffs` plus the
  accounting constants). Engines build one per configuration and pass it
  through their top-level jit boundary as a RUNTIME ARGUMENT — never as a
  baked closure constant — because constant-specialized and
  operand-driven kernels differ by ~1 ulp on XLA (the operand contract;
  see ``repro/core/scheduler.py``'s module comment). The service streams
  the same bundles per tenant, which is what makes a served decision
  bitwise-equal to an engine decision.
* :func:`channel_obs` — one fading-model step, fenced, exactly as the
  engines observe instantaneous CSI. The service does NOT call this: its
  tenants report measured gains with each request (the paper's
  instantaneous-CSI property is what makes that sufficient).
* :func:`decision_step` — the post-observation half: policy step
  (Theorem-2 solve + Bernoulli selection + Eq. 9 queue update for
  ``proposed``), TDMA comm-time and average-power accounting through the
  mesh-invariant blocked reduction.
* :func:`uses_fused` — the one place that picks between the two
  implementations of that decision: the stitched jnp ``decision_step``
  (every policy) and the fused Pallas kernel (``proposed`` only,
  :func:`make_fused_decision` and its sharded and batched twins).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.channel import ChannelConfig
from repro.core.scheduler import (SchedulerConfig, SolveCoeffs, coeff_rate,
                                  solve_coeffs)
from repro.fl.sharding import blocked_total


# The ``solver`` names every engine, runner and the service accept.
SOLVERS = ("jnp", "pallas_fused")


def uses_fused(solver: str, policy: str) -> bool:
    """True iff ``(solver, policy)`` runs the fused decision kernel.

    ``"pallas_fused"`` serves ``proposed`` through
    ``kernels/decision_fused.py``; every other policy, and every policy
    under ``"jnp"``, runs the stitched :func:`decision_step`, which the
    fused path is bitwise-equal to. Any other solver name raises.
    """
    if solver not in SOLVERS:
        raise ValueError(f"unknown solver {solver!r} (want one of "
                         f"{', '.join(repr(s) for s in SOLVERS)})")
    return solver == "pallas_fused" and policy == "proposed"


class AccountCoeffs(NamedTuple):
    """Scalar operands of the per-round accounting island."""

    ell: jax.Array   # model_bits per upload (Eq. 8 numerator)
    bw: jax.Array    # bandwidth B (rate factor)
    n0: jax.Array    # noise power N0 (rate divisor)


class DecisionCoeffs(NamedTuple):
    """Everything scalar the decision layer consumes, as one pytree."""

    solve: SolveCoeffs
    acct: AccountCoeffs


def account_coeffs(scfg: SchedulerConfig,
                   ch: ChannelConfig) -> AccountCoeffs:
    """Fold the accounting constants on the host (f32, once)."""
    f = np.float32
    return AccountCoeffs(ell=f(scfg.model_bits), bw=f(ch.bandwidth_hz),
                         n0=f(ch.noise_power))


def decision_coeffs(scfg: SchedulerConfig,
                    ch: ChannelConfig) -> DecisionCoeffs:
    """The full per-config coefficient bundle (host numpy f32 leaves).

    Pass the result INTO the engine's jitted entry point as an argument —
    the operand contract above — not into a closure.
    """
    return DecisionCoeffs(solve=solve_coeffs(scfg, ch),
                          acct=account_coeffs(scfg, ch))


def channel_obs(channel_step, k_ch, ch_state):
    """One fenced fading-model step: ``(gains, ch_state')``.

    The barrier pins the step outputs so the consumer chains (rate/log2,
    the training gather) cannot fuse INTO the step computations — XLA makes
    that choice per surrounding program, which would drift f32 results by a
    ulp per round and break the grid <-> run_simulation_scan bitwise
    contract (tests/test_grid.py).
    """
    gains, ch_state = channel_step(k_ch, ch_state)
    return jax.lax.optimization_barrier((gains, ch_state))


def _fit_account_axis(contrib: jax.Array, acct_len: Optional[int]):
    """Slice/zero-pad a padded client axis to the tenant's accounting
    length ``acct_len`` (= ``padded_len(n_real)``), so the blocked reduce
    associates exactly as the engine's (n_real,) reduce does. The adjusted
    lanes are exact zeros, which cannot change any block partial."""
    if acct_len is None:
        return contrib
    n = contrib.shape[-1]
    if n >= acct_len:
        return contrib[..., :acct_len]
    return jnp.pad(contrib, (0, acct_len - n))


def decision_step(policy_step, acct: AccountCoeffs, k_sel, gains, pol_state,
                  *, valid=None, acct_len: Optional[int] = None):
    """The per-round decision + accounting, shared verbatim by the scan
    engine, the grid, the client-sharded sequential runner, and the online
    service.

    ``policy_step(k_sel, gains, state) -> (sel, q, p, state')`` is any
    fenced policy (the registry's, or the service's coefficient-driven
    ones); ``k_sel`` passes through untouched, so raw-draw-carrying callers
    hand the pre-drawn raws in its place. Returns
    ``(sel, q, p, t_comm, power, n_sel, pol_state')``.

    Accounting: comm time is the TDMA sum over selected clients of
    ell / rate (Eq. 8 denominator); power is sum_n P_n q_n this round. The
    island is fenced on both sides — its log2 chain otherwise fuses with
    whatever the surrounding program offers — and the sums run through the
    fixed-block mesh-invariant reduce so the client-sharded engine
    reproduces them bit for bit on any mesh.

    ``valid`` / ``acct_len`` are the service's bucket-padding hooks: a
    boolean mask of real (non-pad) lanes, and the tenant's real accounting
    length. Engines pass neither — their client axis is never padded — and
    the default path is bit-for-bit the historic engine expression.
    """
    sel, q, p, pol_state = jax.lax.optimization_barrier(
        policy_step(k_sel, gains, pol_state))
    rate = coeff_rate(gains, p, acct)
    contrib = jnp.where(sel, acct.ell / jnp.maximum(rate, 1e-9), 0.0)
    pq = p * q if valid is None else jnp.where(valid, p * q, 0.0)
    t_comm, power = jax.lax.optimization_barrier(
        (blocked_total(_fit_account_axis(contrib, acct_len)),
         blocked_total(_fit_account_axis(pq, acct_len))))
    return sel, q, p, t_comm, power, jnp.sum(sel), pol_state


def make_fused_decision(scfg: SchedulerConfig, co: DecisionCoeffs, *,
                        block: Optional[int] = None,
                        interpret: Optional[bool] = None):
    """A :func:`decision_step` drop-in that serves the ``proposed`` policy
    through the fused Pallas megakernel (``kernels/decision_fused.py``).

    ``co`` is the caller's coefficient bundle — typically TRACED leaves
    passed through the engine's jit boundary (the operand contract), which
    the wrapper packs into the kernel's (14,) operand vector. The returned
    callable has ``decision_step``'s exact signature; ``policy_step`` and
    ``acct`` are accepted and ignored (the kernel owns the full decision,
    and the accounting scalars ride in the operand vector), so engines can
    swap it in at the decision layer without touching their policy wiring.

    What stays stitched, and why it is still bitwise-equal to
    ``decision_step`` + ``make_policy("proposed", coeffs=...)``:

    * the selection uniforms are drawn here with
      :func:`repro.core.policies.draw_selection_uniform` — the same draw,
      key and dtype ``sample_selection`` performs inside the policy step;
    * the guarantee-one fallback (global ``argmax(q)``) replays
      ``selection_from_uniform``'s exact ops on the kernel's q;
    * the comm-time/power summands are REFOLDED here from the fenced
      (sel, q, p) with ``decision_step``'s exact expressions — not taken
      from the kernel's per-lane outputs — because XLA CPU rounds the
      scalar (width-1) ``log2`` one ulp apart from every vectorized
      width, and the kernel always evaluates at block width while the
      stitched oracle evaluates at N (N = 1 would diverge). The sharded
      twin (``fl/client_shard.py::_sharded_proposed_fused``) makes the
      same choice; the bucket-batched service consumes the kernel
      summands directly, where widths are never 1.

    ``valid`` doubles as the PR-6 population activity mask: the population
    core passes ``valid=active``, and the kernel applies it BOTH as the
    q -> 0 pre-selection mask and as the expected-power accounting mask —
    the same two uses the stitched masked policy + ``decision_step``
    make of it.
    """
    from repro.core.policies import PolicyState, draw_selection_uniform
    from repro.kernels.decision_fused import (decision_fused,
                                              pack_decision_operands)
    ops = pack_decision_operands(co.solve, co.acct)
    kw = {} if block is None else {"block": block}

    def fused_decision(policy_step, acct, k_sel, gains, pol_state, *,
                       valid=None, acct_len: Optional[int] = None):
        del policy_step, acct  # the kernel IS the policy + accounting
        u = draw_selection_uniform(k_sel, gains.shape[0])
        sel_raw, q, p, z_new, _tc, _pq = decision_fused(
            gains, pol_state.z, u, ops, active=valid, valid=valid,
            interpret=interpret, **kw)
        sel_raw, q, p, z_new = jax.lax.optimization_barrier(
            (sel_raw, q, p, z_new))
        if scfg.guarantee_one:
            none = ~jnp.any(sel_raw)
            forced = jnp.zeros_like(sel_raw).at[jnp.argmax(q)].set(True)
            sel = jnp.where(none, forced, sel_raw)
        else:
            sel = sel_raw
        rate = coeff_rate(gains, p, co.acct)
        contrib = jnp.where(sel, co.acct.ell / jnp.maximum(rate, 1e-9), 0.0)
        pq = p * q if valid is None else jnp.where(valid, p * q, 0.0)
        t_comm, power = jax.lax.optimization_barrier(
            (blocked_total(_fit_account_axis(contrib, acct_len)),
             blocked_total(_fit_account_axis(pq, acct_len))))
        st = PolicyState(z_new, pol_state.aux, pol_state.t + 1)
        return sel, q, p, t_comm, power, jnp.sum(sel), st

    return fused_decision
