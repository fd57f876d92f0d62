"""Per-bucket serving step: one ``jit(vmap)`` over a padded tenant batch.

The serving pipeline per request is EXACTLY the engines' decision layer
(``repro/fl/decision.py``): Theorem-2 solve -> Bernoulli selection ->
Eq. 9 queue update (for ``proposed``) -> TDMA comm-time / power
accounting. What this module adds is the multi-tenant batched form:

* every tenant's scalar configuration is a row of a stacked coefficient
  pytree (the ``SolveCoeffs`` operand form of ``repro/core/scheduler.py``
  for ``proposed``; small exact-op bundles for the baselines), so ONE
  compiled program serves heterogeneous tenants — no per-tenant dispatch,
  no recompilation per configuration;
* the client axis is padded to the bucket's power-of-two width with
  documented fills that provably cannot influence a real lane (pad
  selection-uniforms 2.0 > any q; pad scores -1.0 below any real score;
  pad gains 0.0 below any clipped channel gain, and the solve maps
  gains=0 to q = q_floor, which can never win the guarantee-one argmax
  over a real lane);
* the accounting reduce is sliced/zero-padded to the tenant's real
  ``padded_len(n)`` (``acct_len``) so its fixed-block association is the
  engine's own;
* the bucket's stacked queue state is DONATED to the step, so serving
  updates Z in place — no state copies per request.

A step is a plain jitted function of runtime operands: tenant count T and
batch size enter only as operand SHAPES, so one step instance serves a
bucket across admissions, evictions, and every power-of-two batch size
(each shape compiles once — ``SchedulerService.warmup`` pre-compiles the
batch shapes off the serving path, and the staged/legacy batch builders in
``service/batching.py`` feed the same program identical arrays, which is
what makes their bitwise parity a build-layer property, not a numeric
one).

Bitwise contract: with ``solver="jnp"`` a served (sel, q, P) row —
sliced to the tenant's real N — is bitwise-equal to what
``run_simulation_scan`` computes for that tenant's configuration on the
same gains and selection draws, because both sides run the same
coefficient-operand program (the operand contract,
``repro/core/scheduler.py``).

``solver="pallas_fused"`` serves ``proposed`` buckets through the fused
decision megakernel (``kernels/decision_fused.py``): because pallas
calls don't batch under vmap, the kernel itself is NATIVELY bucket-
batched — a (B, N/block) grid with one (14,) operand row per bucket
slot — and only the cheap guarantee/accounting epilogue runs under
``jit(vmap)``. Coefficients stay runtime operands, so heterogeneous
tenants batch in one program and the served rows keep the full BITWISE
contract.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.channel import ChannelConfig
from repro.core.policies import PolicyState, fence_step
from repro.core.scheduler import (GreedyCoeffs, SchedulerConfig,
                                  SolveCoeffs, UniformCoeffs, greedy_coeffs,
                                  greedy_decide, selection_from_uniform,
                                  solve_coeffs, solve_round_coeffs,
                                  uniform_coeffs, uniform_decide,
                                  update_queues_z)
from repro.fl.decision import decision_step

# Policies the service can serve: those whose PRNG consumption is split out
# of the step (repro.core.policies.POLICY_DRAWS), so requests can carry the
# raw draws and replay is deterministic. The other registry policies need
# global normalizations over hidden per-client state (update-norm sums,
# age forcing) that an instantaneous-CSI request cannot carry.
SERVICE_POLICIES = ("proposed", "uniform", "greedy_channel")


def policy_coeffs(policy: str, scfg: SchedulerConfig, ch: ChannelConfig,
                  m_avg: float = 0.0):
    """One tenant's policy-coefficient bundle (host numpy leaves).

    Products fold in float64 exactly as a Python-float trace would bake
    them, so coefficient-driven and config-driven steps agree bit for bit
    (the bundles and their decision cores live in
    ``repro.core.scheduler`` — one home for the math, engines and service
    alike).
    """
    if policy == "proposed":
        return solve_coeffs(scfg, ch)
    if policy == "uniform":
        return uniform_coeffs(scfg.n_clients, m_avg, ch)
    if policy == "greedy_channel":
        return greedy_coeffs(scfg.n_clients, m_avg, ch)
    raise ValueError(f"policy {policy!r} is not servable "
                     f"(servable: {SERVICE_POLICIES})")


# --------------------------------------------------------------------------
# Per-tenant policy cores over coefficient rows. Each mirrors the registry
# step (repro/core/policies.py) op for op; the raws arrive with the request
# (POLICY_DRAWS split), exactly like the client-sharded engine's recipe.
# --------------------------------------------------------------------------

def _proposed_core(guarantee_one: bool):
    def core(u, gains, st: PolicyState, c: SolveCoeffs):
        q, p = solve_round_coeffs(gains, st.z, c)
        sel = selection_from_uniform(u, q, guarantee_one)
        z = update_queues_z(st.z, q, p, c)
        return sel, q, p, PolicyState(z, st.aux, st.t + 1)

    return core


def _uniform_core(guarantee_one: bool):
    # core.scheduler.uniform_decide IS the engine's uniform math — every
    # float op in it is individually correctly-rounded with no contraction
    # pair, so constant-config and operand-config runs agree bit for bit
    def core(raw, gains, st: PolicyState, c: UniformCoeffs):
        sel, q, p = uniform_decide(raw, c)
        return sel, q, p, PolicyState(st.z, st.aux, st.t + 1)

    return core


def _greedy_core(guarantee_one: bool):
    def core(raw, gains, st: PolicyState, c: GreedyCoeffs):
        sel, q, p = greedy_decide(gains, c)
        return sel, q, p, PolicyState(st.z, st.aux, st.t + 1)

    return core


_POLICY_CORES = {
    "proposed": _proposed_core,
    "uniform": _uniform_core,
    "greedy_channel": _greedy_core,
}


def step_signature(bkey, n_tenants: int, batch: int, solver: str) -> tuple:
    """The compile-cache signature of one bucket-step dispatch.

    A step compiles one program variant per (tenant count T, padded batch
    size B) operand-shape pair — T and B enter only as shapes (module
    docstring) — within the program family the bucket key + solver
    select. The batcher keys its host-side recompile tracking
    (``repro.obs``'s ``CompileTracker``) on exactly this tuple so the
    tracked misses mirror the jit cache one-for-one: a miss here IS a
    fresh XLA compile on the serving path (the PR-8 latency-cliff
    pathology, now a visible counter instead of a silent p99 spike).
    """
    return (bkey, int(n_tenants), int(batch), solver)


class PackedLayout(NamedTuple):
    """Columns of one row of a bucket step's packed (B, W) uint32 output:
    ``sel`` (0/1), ``q`` and ``p`` (float32 bits) over the bucket's lanes,
    then one column each of ``t_comm``, ``power`` (float32 bits) and
    ``n_sel`` (int32 bits)."""

    sel: slice
    q: slice
    p: slice
    t_comm: int
    power: int
    n_sel: int
    width: int


def packed_layout(n_bucket: int) -> PackedLayout:
    """The packed output's column layout for a bucket of ``n_bucket``
    lanes (:func:`pack_outputs` writes it, :func:`unpack_outputs` reads
    it)."""
    nb = int(n_bucket)
    return PackedLayout(sel=slice(0, nb), q=slice(nb, 2 * nb),
                        p=slice(2 * nb, 3 * nb), t_comm=3 * nb,
                        power=3 * nb + 1, n_sel=3 * nb + 2, width=3 * nb + 3)


def pack_outputs(sel, q, p, t_comm, power, n_sel):
    """The six per-row decision outputs as one (B, W) uint32 array, in
    :func:`packed_layout`'s columns, so the host pulls a serve group in
    one device-to-host transfer. Bitcasts only: every value keeps its bits
    (-0.0, denormals, inf and NaN payloads included)."""
    def bits(x):
        x = jax.lax.bitcast_convert_type(x, jnp.uint32)
        return x if x.ndim == 2 else x[:, None]

    # n_sel counts at most n_bucket lanes: int32 holds it exactly (the
    # sum is int64 under JAX_ENABLE_X64)
    return jnp.concatenate(
        [sel.astype(jnp.uint32), bits(q), bits(p), bits(t_comm),
         bits(power), bits(n_sel.astype(jnp.int32))], axis=1)


def unpack_outputs(packed: np.ndarray, n_bucket: int):
    """``(sel, q, p, t_comm, power, n_sel)`` of a pulled packed output:
    views of the one host buffer (bool ``sel`` is the one copy), with the
    dtypes the step computed them in."""
    lay = packed_layout(n_bucket)
    return (packed[:, lay.sel].astype(bool),
            packed[:, lay.q].view(np.float32),
            packed[:, lay.p].view(np.float32),
            packed[:, lay.t_comm].view(np.float32),
            packed[:, lay.power].view(np.float32),
            packed[:, lay.n_sel].view(np.int32))


def make_bucket_step(policy: str, n_bucket: int, acct_len: int,
                     guarantee_one: bool, fused: bool = False):
    """Build the jitted batched serving step for one bucket shape.

    Returns ``bucket_step(state, coeffs, acct, n_real, rows, gains, raw)
    -> (packed, state')`` where ``packed`` is the batch's six decision
    outputs (sel, q, p, t_comm, power, n_sel) in one (B, W) uint32 array
    (:func:`pack_outputs`; :func:`unpack_outputs` takes it apart on the
    host), so a serve group costs one device-to-host transfer, and

    * ``state`` — the bucket's stacked :class:`PolicyState` (leaves
      (T, n_bucket) / (T,)). DONATED: the returned state reuses its
      buffers, so per-request serving never copies tenant queues.
    * ``coeffs`` / ``acct`` / ``n_real`` — stacked per-tenant scalars
      ((T,) leaves), gathered by row inside the step.
    * ``rows`` — (B,) int32 tenant rows for this batch; pad entries point
      one past the end (T), where the gather clamps (garbage compute,
      masked out) and the scatter drops (state untouched) — pad lanes can
      never alter a real tenant's bits.
    * ``gains`` (B, n_bucket) and ``raw`` (stacked policy raws) — padded
      request payloads.

    One compiled program per (bucket, B) shape; batch sizes are padded to
    powers of two by the batcher, so the number of compilations stays
    logarithmic in the peak batch size.

    ``fused=True`` (``proposed`` only) serves the whole batch through the
    natively bucket-batched fused megakernel — solve + selection + Eq. 9
    + accounting summands in one (B, n_bucket/block) grid — with the
    guarantee-one fallback and the blocked accounting folds vmapped over
    rows outside, replaying ``selection_from_uniform``'s and
    ``decision_step``'s exact ops. Bitwise-equal to the default stitched
    rows (tests/test_decision_fused.py); every scalar rides the operand
    rows, so heterogeneous tenants share the program.
    """
    core = _POLICY_CORES[policy](guarantee_one)
    if fused and policy != "proposed":
        raise ValueError("fused=True needs policy='proposed' (the only "
                         "policy with a fused decision kernel)")

    def one(raw_r, gains_r, st_r, c_r, a_r, nr):
        valid = jnp.arange(n_bucket, dtype=jnp.int32) < nr
        step = fence_step(lambda k, g, s: core(k, g, s, c_r))
        return decision_step(step, a_r, raw_r, gains_r, st_r,
                             valid=valid, acct_len=acct_len)

    def fused_rows(raw, gains, st_rows, c_rows, a_rows, nr_rows):
        from repro.fl.decision import _fit_account_axis
        from repro.fl.sharding import blocked_total
        from repro.kernels.decision_fused import (decision_fused_batched,
                                                  pack_decision_operands)
        ops = jax.vmap(pack_decision_operands)(c_rows, a_rows)  # (B, 14)
        valid = (jnp.arange(n_bucket, dtype=jnp.int32)[None, :]
                 < nr_rows[:, None])
        sel_raw, q, p, z_new, tc, pq = jax.lax.optimization_barrier(
            decision_fused_batched(gains, st_rows.z, raw, ops, valid=valid))

        def finish(sel_r, q_r, tc_r, pq_r):
            if guarantee_one:
                none = ~jnp.any(sel_r)
                forced = jnp.zeros_like(sel_r).at[jnp.argmax(q_r)].set(True)
                sel_r = jnp.where(none, forced, sel_r)
            contrib = jnp.where(sel_r, tc_r, 0.0)
            t_comm, power = jax.lax.optimization_barrier(
                (blocked_total(_fit_account_axis(contrib, acct_len)),
                 blocked_total(_fit_account_axis(pq_r, acct_len))))
            return sel_r, t_comm, power, jnp.sum(sel_r)

        sel, t_comm, power, n_sel = jax.vmap(finish)(sel_raw, q, tc, pq)
        st_new = PolicyState(z_new, st_rows.aux, st_rows.t + 1)
        return sel, q, p, t_comm, power, n_sel, st_new

    @functools.partial(jax.jit, donate_argnums=(0,))
    def bucket_step(state, coeffs, acct, n_real, rows, gains, raw):
        st_rows = jax.tree.map(lambda a: a[rows], state)
        c_rows = jax.tree.map(lambda a: a[rows], coeffs)
        a_rows = jax.tree.map(lambda a: a[rows], acct)
        nr_rows = n_real[rows]
        if fused:
            sel, q, p, t_comm, power, n_sel, st_new = fused_rows(
                raw, gains, st_rows, c_rows, a_rows, nr_rows)
        else:
            sel, q, p, t_comm, power, n_sel, st_new = jax.vmap(one)(
                raw, gains, st_rows, c_rows, a_rows, nr_rows)
        new_state = jax.tree.map(
            lambda buf, upd: buf.at[rows].set(upd, mode="drop"),
            state, st_new)
        return pack_outputs(sel, q, p, t_comm, power, n_sel), new_state

    return bucket_step
