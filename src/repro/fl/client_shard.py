"""Client-axis sharded scheduling: the per-round N-client pipeline on a mesh.

The paper's scheduler consumes only instantaneous CSI, so the aggregator
re-solves Theorem 2 for EVERY client EVERY round — at the ROADMAP's
millions-of-users scale that (N,)-shaped channel -> solve -> select ->
account pipeline is the hot path, and until this module it materialized all
N clients on one device (a full-(N,) participant pack, a full O(N log N)
sort for the uniform baseline's threshold). Here the client
axis is sharded over a ``'client'`` device mesh axis in ONE ``shard_map``:

* each device steps its N/D slice of the fading process, runs its slice of
  the Theorem-2 decision (the jnp closed form, or under
  ``solver="pallas_fused"`` the fused decision kernel, per shard), and
  Bernoulli-samples its participants locally;
* the global pack becomes a per-shard pack + cross-shard merge of
  the <= m_cap packed participant indices;
* the uniform baseline's full sort becomes a per-shard ``lax.top_k`` +
  k-way merge of the (D * k) candidate scores;
* only scalars (the fenced accounting island: t_comm, power, n_selected,
  plus the queue-drift bookkeeping they imply) and the <= m_cap packed
  indices cross devices, via ``psum`` / ``all_gather``.

Numeric contract (tests/test_client_sharded.py), mirroring the grid's and
the participant-sharded round's per-mesh contracts:

* mesh size 1 is BITWISE-identical to ``run_simulation_scan`` — the raw
  PRNG draws happen full-shape OUTSIDE the shard_map (the same traced draw
  as the sequential engine: ``CHANNEL_RAW`` / ``POLICY_DRAWS`` split each
  step into its PRNG half and its elementwise half), and every elementwise
  stage is the same fenced code the sequential step runs.
* accounting association is mesh-invariant: the reductions always
  associate as ``ACCOUNT_BLOCKS`` fixed blocks (``fl/sharding.py``), so
  the sequential engine and every mesh width add the same partials in the
  same order; float accounting agrees across meshes to ~1 ulp (the
  residual is per-lane EMISSION drift of the operand-driven solve, not
  reduction reassociation — see fl/sharding.py). Thresholds, argmaxes,
  packs, and merges are selections, not arithmetic — so integer
  accounting (n_selected, packed indices) stays exact in practice (pinned
  by fixed seeds; a selection could in principle flip if a raw draw lands
  inside the ~1 ulp cross-mesh q drift — see fl/sharding.py).
* trained metrics (test_acc) drift only by reduction re-association in the
  surrounding program, ~1 ulp/round, like the other sharded paths.

Policies with a sharded implementation: ``proposed``, ``uniform``,
``greedy_channel`` (``POLICY_DRAWS``). The others need global
normalizations (update-norm sums, global age forcing) with no exact
sharded form yet and are rejected up front.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import ChannelConfig, SchedulerConfig
from repro.core.channel import CHANNEL_RAW, make_channel
from repro.core.fences import pin
from repro.core.policies import (POLICIES, POLICY_DRAWS, PolicyState,
                                 init_policy_state, make_policy)
from repro.core.scheduler import (coeff_rate, greedy_coeffs,
                                  solve_round_coeffs, uniform_coeffs,
                                  uniform_draw_m, update_queues_z)
from repro.fl.decision import (DecisionCoeffs, channel_obs, decision_coeffs,
                               decision_step, uses_fused)
from repro.fl.round import pack_participants
from repro.fl.sharding import (ACCOUNT_BLOCKS, blocked_total_sharded,
                               pad_client_axis, padded_len, shard_map)
from repro.obs import metrics as obs_metrics
from repro.obs.instrument import EngineInstruments
from repro.obs.profile import span

_I32_MAX = jnp.iinfo(jnp.int32).max

# Pad fills for the client axis of each model's raw draws: uniforms feeding
# log() pad with 1.0 (log 1 = 0, no -inf), normals with 0.0. Pad lanes are
# masked out of every selection and reduction; the fills only need to keep
# the elementwise math finite.
CHANNEL_RAW_PAD = {
    "rayleigh": 1.0,
    "rician": 0.0,
    "lognormal": (1.0, 0.0),
    "gauss_markov": 0.0,
    "mobility": 0.0,
    # outage_burst: (ray uniform -> 1.0 keeps log finite, transition
    # uniform -> 1.0 never enters an outage on a pad lane)
    "outage_burst": (1.0, 1.0),
}

# Policy raw fills: proposed pads its selection uniforms with 2.0 (never
# < q <= 1), uniform pads its scores with -1.0 (below any real score in
# [0, 1), so never at/above the threshold).
POLICY_RAW_PAD = {
    "proposed": 2.0,
    "uniform": {"take": 0.0, "scores": -1.0},
    "greedy_channel": (),
}


def _pad_raw(raw, fills, n_pad: int):
    """Pad every client-axis leaf of a raw-draw pytree (scalars pass)."""
    return jax.tree.map(
        lambda x, f: x if jnp.ndim(x) == 0
        else pad_client_axis(x, n_pad, f), raw, fills)


def _client_spec(x):
    """PartitionSpec for a raw/state leaf: last axis is the client axis."""
    nd = jnp.ndim(x)
    if nd == 0:
        return P()
    return P(*([None] * (nd - 1) + ["client"]))


def _axis_start(axis_name: str, n_local: int):
    return jax.lax.axis_index(axis_name) * n_local


def _global_argmax(score, local_ids, axis_name):
    """``jnp.argmax`` of a sharded vector: first index attaining the max.

    Selection only (max + index min), so exact on any mesh. ``score`` must
    be -inf on invalid lanes.
    """
    lmax = jnp.max(score)
    larg = local_ids[jnp.argmax(score)]
    gmax = jax.lax.pmax(lmax, axis_name)
    cand = jnp.where(lmax == gmax, larg, _I32_MAX)
    return jax.lax.pmin(cand, axis_name)


def _top_m_threshold(score, m, k_static: int, axis_name):
    """The m-th largest entry of a sharded score vector.

    Per-shard ``lax.top_k`` (k_static >= min(m, n_local) so the union of
    per-shard candidates provably contains the global top-m), an
    ``all_gather`` of the (D, k_static) candidates, and one small sort —
    the distributed replacement for the sequential ``-sort(-scores)[m-1]``.
    Returns the identical VALUE (selection, not arithmetic), so masks built
    from it match the sequential ones bit for bit. ``m`` may be traced.
    """
    cand = jax.lax.top_k(score, k_static)[0]
    merged = jax.lax.all_gather(cand, axis_name).reshape(-1)
    ordered = -jnp.sort(-merged)
    return ordered[m - 1]


def _pack_participants_sharded(sel, q, m_cap: int, n_local: int, axis_name):
    """Per-shard pack + cross-shard merge of the first m_cap participants.

    The sequential engine packs with ``pack_participants``' rank search
    over the full-(N,) prefix count; here each shard packs its own
    selections (ascending local order) and the merge concatenates shards
    in mesh order — ascending GLOBAL order, so the packed indices match
    the sequential ones exactly. Only the (D, m_cap) packed indices/q
    values and the (D,) counts cross devices.
    """
    count = jnp.sum(sel).astype(jnp.int32)
    lidx = jnp.nonzero(sel, size=m_cap, fill_value=0)[0]
    gidx = (lidx + _axis_start(axis_name, n_local)).astype(jnp.int32)
    all_idx = jax.lax.all_gather(gidx, axis_name).reshape(-1)
    all_q = jax.lax.all_gather(q[lidx], axis_name).reshape(-1)
    all_cnt = jax.lax.all_gather(count, axis_name)
    slot_ok = (jnp.arange(m_cap)[None, :] < all_cnt[:, None]).reshape(-1)
    take = jnp.nonzero(slot_ok, size=m_cap, fill_value=0)[0]
    sel_valid = jnp.arange(m_cap) < jnp.sum(all_cnt)
    sel_idx = jnp.where(sel_valid, all_idx[take], 0)
    # q on dead slots never matters (their aggregation weight is exactly
    # 0.0 in both engines); 1.0 keeps the division benign
    q_sel = jnp.where(sel_valid, all_q[take], 1.0)
    return sel_idx, sel_valid, q_sel


# --------------------------------------------------------------------------
# Sharded policy steps (the POLICY_DRAWS subset).
# --------------------------------------------------------------------------

def _sharded_proposed(scfg: SchedulerConfig, ch: ChannelConfig, m_avg,
                      n_real: int, n_local: int, axis_name: str):
    def step(raw, gains, z, aux, t, valid, local_ids, co, active=None,
             n_act=None):
        # the coefficient-driven solve on the runtime bundle — the operand
        # contract the sequential engine shares (repro/core/scheduler.py)
        q, p = solve_round_coeffs(gains, z, co.solve)
        if active is not None:
            # the sequential masked step's q -> 0 on inactive lanes, BEFORE
            # selection and the Eq. 9 charge (repro.core.policies)
            q = jnp.where(active, q, 0.0)
        sel = (raw < q) & valid
        if scfg.guarantee_one:
            none = jax.lax.psum(jnp.sum(sel), axis_name) == 0
            live = valid if active is None else active
            score = jnp.where(live, q, -jnp.inf)
            forced_at = _global_argmax(score, local_ids, axis_name)
            sel = jnp.where(none, local_ids == forced_at, sel)
        z = update_queues_z(z, q, p, co.solve)
        return sel, q, p, z, aux, t + 1

    return step


def _sharded_proposed_fused(scfg: SchedulerConfig, ch: ChannelConfig, m_avg,
                            n_real: int, n_local: int, axis_name: str):
    """The megakernel twin of :func:`_sharded_proposed`: each shard runs
    solve + Bernoulli comparison + Eq. 9 queue update as ONE Pallas pass
    over its (n_local,) slice (``kernels/decision_fused.py``), bitwise-
    equal to the stitched step because the kernel reuses the jnp oracle's
    traced ops on the runtime operand vector. The cross-shard pieces —
    guarantee-one psum/argmax, the blocked accounting reduce in
    ``account_and_pack`` — stay outside, exactly as before (the kernel's
    per-lane comm-time/power summands are recomputed there from the same
    (gains, q, p); the expressions are identical, so the fold is too).
    """
    from repro.kernels.decision_fused import (decision_fused,
                                              pack_decision_operands)

    def step(raw, gains, z, aux, t, valid, local_ids, co, active=None,
             n_act=None):
        ops = pack_decision_operands(co.solve, co.acct)
        sel_raw, q, p, z, _tc, _pq = decision_fused(gains, z, raw, ops,
                                                    active=active)
        sel = sel_raw & valid
        if scfg.guarantee_one:
            none = jax.lax.psum(jnp.sum(sel), axis_name) == 0
            live = valid if active is None else active
            score = jnp.where(live, q, -jnp.inf)
            forced_at = _global_argmax(score, local_ids, axis_name)
            sel = jnp.where(none, local_ids == forced_at, sel)
        return sel, q, p, z, aux, t + 1

    return step


def _sharded_uniform(scfg: SchedulerConfig, ch: ChannelConfig, m_avg,
                     n_real: int, n_local: int, axis_name: str):
    m_hi = int(np.floor(m_avg)) + 1  # static bound: m' in [1, min(m_hi, N)]
    k_static = max(1, min(n_local, min(m_hi, n_real)))
    # the same host-folded f32 coefficients the sequential uniform_decide
    # uses — the scalar math must be f32 in BOTH engines or the mesh-1
    # bitwise contract breaks on the x64 CI leg (Python-float expressions
    # evaluate in f64 there)
    c = uniform_coeffs(n_real, m_avg, ch)

    def step(raw, gains, z, aux, t, valid, local_ids, co, active=None,
             n_act=None):
        take_hi = raw["take"] < (c.m_avg - jnp.floor(c.m_avg))
        if active is None:
            m = uniform_draw_m(take_hi, c.m_avg, c.n)
            scores = jnp.where(valid, raw["scores"], -1.0)
            thresh = _top_m_threshold(scores, m, k_static, axis_name)
            sel = (raw["scores"] >= thresh) & valid
            q = jnp.full((n_local,), c.q_val)
        else:
            # M' clips into the ACTIVE count so the threshold can never
            # tie into inactive (-1-scored) lanes — the mask-hardening of
            # uniform_draw_m, mirrored from the sequential masked step
            m = uniform_draw_m(take_hi, c.m_avg, c.n, n_active=n_act)
            scores = jnp.where(active, raw["scores"], -1.0)
            thresh = _top_m_threshold(scores, m, k_static, axis_name)
            sel = (scores >= thresh) & valid
            q = jnp.where(active,
                          jnp.full((n_local,), c.q_val, jnp.float32), 0.0)
        p = jnp.full((n_local,), c.pn / jnp.maximum(m, 1))
        return sel, q, p, z, aux, t + 1

    return step


def _sharded_greedy(scfg: SchedulerConfig, ch: ChannelConfig, m_avg,
                    n_real: int, n_local: int, axis_name: str):
    c = greedy_coeffs(n_real, m_avg, ch)
    m = int(c.m)
    k_static = max(1, min(n_local, min(m, n_real)))

    def step(raw, gains, z, aux, t, valid, local_ids, co, active=None,
             n_act=None):
        if active is None:
            score = jnp.where(valid, gains, -jnp.inf)
            thresh = _top_m_threshold(score, m, k_static, axis_name)
            sel = (gains >= thresh) & valid
        else:
            m_eff = jnp.clip(c.m, 1, jnp.maximum(n_act, 1))
            score = jnp.where(active, gains, -jnp.inf)
            thresh = _top_m_threshold(score, m_eff, k_static, axis_name)
            sel = (score >= thresh) & valid
        q = sel.astype(jnp.float32)
        p = jnp.full((n_local,), c.pn / jnp.maximum(c.m, 1))
        return sel, q, p, z, aux, t + 1

    return step


_SHARDED_POLICIES = {
    "proposed": _sharded_proposed,
    "uniform": _sharded_uniform,
    "greedy_channel": _sharded_greedy,
}


# --------------------------------------------------------------------------
# The sharded schedule: ONE shard_map over the client mesh axis.
# --------------------------------------------------------------------------

def validate_client_shards(n_shards: int, policy: str, channel: str,
                           devices=None) -> list:
    """Fail fast on unusable mesh/policy/channel combinations."""
    devices = list(devices if devices is not None else jax.devices())
    if not 1 <= n_shards <= len(devices):
        raise ValueError(f"client_shards={n_shards} needs 1.."
                         f"{len(devices)} of the available devices")
    if ACCOUNT_BLOCKS % n_shards:
        raise ValueError(
            f"client_shards={n_shards} must divide ACCOUNT_BLOCKS="
            f"{ACCOUNT_BLOCKS} (the fixed association width of the exact "
            f"accounting reduce; see repro/fl/sharding.py)")
    if policy not in _SHARDED_POLICIES:
        raise ValueError(
            f"policy {policy!r} has no client-sharded implementation "
            f"(sharded: {sorted(_SHARDED_POLICIES)}); it needs a global "
            "normalization with no exact sharded form")
    if channel not in CHANNEL_RAW:
        raise ValueError(f"unknown channel model {channel!r} "
                         f"(registered: {sorted(CHANNEL_RAW)})")
    return devices[:n_shards]


def _validate_m_avg(policy: str, m_avg: float):
    # mirror make_policy's check: a baseline with m_avg = 0 would silently
    # run with q = 0 (and a 1/q aggregation blowup downstream)
    if POLICIES[policy][2] and not m_avg > 0.0:
        raise ValueError(f"policy {policy!r} needs m_avg > 0 (matched "
                         f"average participation), got {m_avg!r}")


def make_sharded_schedule(sim_policy: str, sim_channel: str,
                          channel_params: tuple, scfg: SchedulerConfig,
                          ch: ChannelConfig, sigmas: jax.Array, *,
                          n_shards: int, m_cap: int, m_avg: float = 0.0,
                          population=None, devices=None, fused: bool = False,
                          mesh=None):
    """Build the one-``shard_map`` scheduling step for one round.

    Returns ``schedule(raw_ch, raw_pol, pol_state, ch_state, co) ->
    (t_comm, power, n_sel, sel_idx, sel_valid, q_sel, pol_state',
    ch_state')`` where the raws are the FULL-SHAPE (N,) PRNG draws of
    ``draw_channel_raw`` / ``draw_policy_raw`` (drawn outside, so their
    bits are mesh-invariant), the states carry the sequential engines'
    unpadded (N,) layout — padding to whole accounting blocks happens
    inside, per call — and ``co`` is the runtime ``DecisionCoeffs`` bundle
    (replicated across the mesh; the operand contract of
    ``repro/fl/decision.py``).

    ``population`` (a ``PopulationConfig`` or its param tuple) switches on
    the dynamic-population round: the signature becomes ``schedule(raw_ch,
    raw_pol, (raw_churn, raw_fail), pol_state, (ch_state, active), co)``
    with the churn/failure uniforms drawn full-shape outside (the
    ``fold_in`` side-channels of ``repro.fl.population``) and the activity
    mask riding the channel-state slot, exactly as the sequential
    population round carries it. Inactive lanes follow the pad-lane
    hygiene: never selected, q = 0, excluded from the power accounting;
    stragglers (selected-but-failed) keep their airtime and count but are
    dropped from the packed participants.

    ``fused=True`` (``policy="proposed"`` only; callers pass
    ``repro.fl.decision.uses_fused``)
    swaps the per-shard policy step for the fused Pallas megakernel
    variant — solve + selection + Eq. 9 in one pass per shard slice,
    bitwise-equal to the stitched step (tests/test_decision_fused.py).

    ``mesh`` rides a caller-owned mesh carrying a ``'client'`` axis of
    extent ``n_shards`` (the composed round passes the shared
    ``('client', 'part')`` mesh of ``fl/sharding.py::make_mesh2d``). The
    specs below name only ``'client'``, so any extra axes are implicitly
    replicated — every 'part' column runs an identical copy of the
    per-shard schedule and the numeric contract is unchanged.
    """
    n = int(sigmas.shape[0])
    if mesh is not None:
        if "client" not in mesh.axis_names:
            raise ValueError(f"shared mesh {mesh.axis_names} has no "
                             "'client' axis")
        if mesh.shape["client"] != n_shards:
            raise ValueError(
                f"client_shards={n_shards} != mesh 'client' extent "
                f"{mesh.shape['client']}")
        validate_client_shards(n_shards, sim_policy, sim_channel,
                               list(mesh.devices.flat))
    else:
        devices = validate_client_shards(n_shards, sim_policy, sim_channel,
                                         devices)
        mesh = Mesh(np.array(devices), ("client",))
    _validate_m_avg(sim_policy, m_avg)
    pcfg = None
    if population is not None:
        from repro.fl.population import population_config
        pcfg = population_config(population)
    n_pad = padded_len(n)
    n_local = n_pad // n_shards
    ckw = dict(channel_params)
    _, chan_apply = CHANNEL_RAW[sim_channel]
    if fused and sim_policy != "proposed":
        raise ValueError("fused=True needs policy='proposed' (the only "
                         "policy with a fused decision kernel)")
    make_step = (_sharded_proposed_fused if fused
                 else _SHARDED_POLICIES[sim_policy])
    policy_step = make_step(scfg, ch, m_avg, n, n_local, "client")
    sig_pad = pad_client_axis(sigmas, n_pad, 0.0)

    def account_and_pack(gains, valid, sel, q, p, delivered, co):
        # the fenced accounting island + participant pack shared by both
        # round variants (fixed-population: delivered IS sel)
        rate = coeff_rate(gains, p, co.acct)
        t_comm = blocked_total_sharded(
            jnp.where(sel, co.acct.ell / jnp.maximum(rate, 1e-9), 0.0),
            "client", n_shards)
        power = blocked_total_sharded(
            jnp.where(valid, p * q, 0.0), "client", n_shards)
        t_comm, power = jax.lax.optimization_barrier((t_comm, power))
        n_sel = jax.lax.psum(jnp.sum(sel), "client")
        sel_idx, sel_valid, q_sel = _pack_participants_sharded(
            delivered, q, m_cap, n_local, "client")
        return t_comm, power, n_sel, sel_idx, sel_valid, q_sel

    def shard_body(raw_ch, raw_pol, z, aux, t, cst, sig, co):
        local_ids = (_axis_start("client", n_local)
                     + jnp.arange(n_local, dtype=jnp.int32))
        valid = local_ids < n
        raw_ch, cst, sig = pin((raw_ch, cst, sig))
        gains, cst = chan_apply(raw_ch, cst, sig, ch, **ckw)
        # same fence discipline as the sequential round core: the step
        # outputs are pinned so downstream chains cannot fuse into them
        gains, cst = jax.lax.optimization_barrier((gains, cst))
        raw_pol, z, aux = pin((raw_pol, z, aux))
        sel, q, p, z, aux, t = jax.lax.optimization_barrier(
            policy_step(raw_pol, gains, z, aux, t, valid, local_ids, co))
        t_comm, power, n_sel, sel_idx, sel_valid, q_sel = account_and_pack(
            gains, valid, sel, q, p, sel, co)
        return (t_comm, power, n_sel, sel_idx, sel_valid, q_sel, z, aux, t,
                cst)

    def shard_body_pop(raw_ch, raw_pol, raw_churn, raw_fail, active, z,
                       aux, t, cst, sig, co):
        local_ids = (_axis_start("client", n_local)
                     + jnp.arange(n_local, dtype=jnp.int32))
        valid = local_ids < n
        raw_ch, cst, sig = pin((raw_ch, cst, sig))
        gains, cst = chan_apply(raw_ch, cst, sig, ch, **ckw)
        gains, cst = jax.lax.optimization_barrier((gains, cst))
        raw_pol, z, aux, raw_churn, raw_fail, active = pin(
            (raw_pol, z, aux, raw_churn, raw_fail, active))
        # churn: the per-lane Markov step of population.churn_step, with
        # its never-empty guarantee distributed exactly like guarantee_one
        # (psum the count, global-argmax the forced lane). Pad lanes can
        # never activate (& valid), matching their dead-lane hygiene.
        new = (jnp.where(active, raw_churn >= pcfg.p_leave,
                         raw_churn < pcfg.p_join) & valid)
        none = jax.lax.psum(jnp.sum(new), "client") == 0
        forced_at = _global_argmax(
            jnp.where(valid, raw_churn, -jnp.inf), local_ids, "client")
        active = jnp.where(none, local_ids == forced_at, new)
        n_act = jax.lax.psum(jnp.sum(active.astype(jnp.int32)), "client")
        sel, q, p, z, aux, t = jax.lax.optimization_barrier(
            policy_step(raw_pol, gains, z, aux, t, valid, local_ids, co,
                        active, n_act))
        # stragglers: airtime/count charged on sel, training sees delivered
        delivered = sel & ~(sel & (raw_fail < pcfg.p_fail))
        t_comm, power, n_sel, sel_idx, sel_valid, q_sel = account_and_pack(
            gains, valid, sel, q, p, delivered, co)
        return (t_comm, power, n_sel, sel_idx, sel_valid, q_sel, z, aux, t,
                cst, active)

    dummy_key = jax.random.PRNGKey(0)
    raw_ch_eg = jax.eval_shape(
        lambda k: draw_channel_raw(sim_channel, k, n, ckw), dummy_key)
    raw_pol_eg = jax.eval_shape(
        lambda k: draw_policy_raw(sim_policy, k, n), dummy_key)
    co_eg = decision_coeffs(scfg, ch)
    co_spec = jax.tree.map(lambda _: P(), co_eg)  # coeffs: replicated
    raw_specs = (jax.tree.map(_client_spec, raw_ch_eg),
                 jax.tree.map(_client_spec, raw_pol_eg))
    if pcfg is None:
        in_specs = raw_specs + (
            P("client"), P("client"), P(), P(None, "client"), P("client"),
            co_spec)
        out_specs = (P(), P(), P(), P(), P(), P(), P("client"),
                     P("client"), P(), P(None, "client"))
        sharded = shard_map(shard_body, mesh=mesh, in_specs=in_specs,
                            out_specs=out_specs)
    else:
        in_specs = raw_specs + (
            P("client"), P("client"), P("client"),
            P("client"), P("client"), P(), P(None, "client"), P("client"),
            co_spec)
        out_specs = (P(), P(), P(), P(), P(), P(), P("client"),
                     P("client"), P(), P(None, "client"), P("client"))
        sharded = shard_map(shard_body_pop, mesh=mesh, in_specs=in_specs,
                            out_specs=out_specs)

    def constrain(raw):
        # the raws are drawn full-shape OUTSIDE the shard_map (mesh-
        # invariant bits); without a placement hint GSPMD materializes the
        # whole (N,) draw on every device. The constraint shards the draw
        # output across the client mesh — purely a placement choice, the
        # values are untouched (verified bit-exact)
        return jax.tree.map(
            lambda x: x if jnp.ndim(x) == 0
            else jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, _client_spec(x))), raw)

    def schedule(raw_ch, raw_pol, pol_state: PolicyState, ch_state, co):
        raw_ch = _pad_raw(constrain(raw_ch), CHANNEL_RAW_PAD[sim_channel],
                          n_pad)
        raw_pol = _pad_raw(constrain(raw_pol),
                           POLICY_RAW_PAD[sim_policy], n_pad)
        z = pad_client_axis(pol_state.z, n_pad, 0.0)
        aux = pad_client_axis(pol_state.aux, n_pad, 0.0)
        cst = pad_client_axis(ch_state, n_pad, 0.0)
        (t_comm, power, n_sel, sel_idx, sel_valid, q_sel, z, aux, t,
         cst) = sharded(raw_ch, raw_pol, z, aux, pol_state.t, cst, sig_pad,
                        co)
        return (t_comm, power, n_sel, sel_idx, sel_valid, q_sel,
                PolicyState(z[:n], aux[:n], t), cst[..., :n])

    def schedule_pop(raw_ch, raw_pol, raw_pop, pol_state: PolicyState,
                     ch_state, co):
        cst, active = ch_state
        raw_churn, raw_fail = raw_pop
        raw_ch = _pad_raw(constrain(raw_ch), CHANNEL_RAW_PAD[sim_channel],
                          n_pad)
        raw_pol = _pad_raw(constrain(raw_pol),
                           POLICY_RAW_PAD[sim_policy], n_pad)
        # churn/fail pads: any finite value works — pad lanes are fenced
        # out by `& valid` before the uniforms are consumed
        raw_churn = pad_client_axis(constrain(raw_churn), n_pad, 2.0)
        raw_fail = pad_client_axis(constrain(raw_fail), n_pad, 2.0)
        active = pad_client_axis(active, n_pad, False)
        z = pad_client_axis(pol_state.z, n_pad, 0.0)
        aux = pad_client_axis(pol_state.aux, n_pad, 0.0)
        cst = pad_client_axis(cst, n_pad, 0.0)
        (t_comm, power, n_sel, sel_idx, sel_valid, q_sel, z, aux, t, cst,
         active) = sharded(raw_ch, raw_pol, raw_churn, raw_fail, active, z,
                           aux, pol_state.t, cst, sig_pad, co)
        return (t_comm, power, n_sel, sel_idx, sel_valid, q_sel,
                PolicyState(z[:n], aux[:n], t), (cst[..., :n], active[:n]))

    return schedule if pcfg is None else schedule_pop


def draw_channel_raw(channel: str, key, n: int, channel_params):
    draw, _ = CHANNEL_RAW[channel]
    return draw(key, n, **dict(channel_params))


def draw_policy_raw(policy: str, key, n: int):
    return POLICY_DRAWS[policy](key, n)


# --------------------------------------------------------------------------
# Scheduling-only runner: the massive-N path, stepped chunk by chunk.
# --------------------------------------------------------------------------

def _fresh_states(chan, policy: str, n: int, key):
    """Empty queues and the channel's stationary init off
    ``fold_in(key, CHANNEL_INIT_TAG)`` — the engines' side channel, so the
    round-key chain is the scan engine's."""
    from repro.fl.engine import CHANNEL_INIT_TAG

    return (init_policy_state(policy, n),
            chan.init(jax.random.fold_in(key, CHANNEL_INIT_TAG)))


def init_schedule_carry(key, sigmas: jax.Array, ch: ChannelConfig, *,
                        policy: str = "proposed", channel: str = "rayleigh",
                        channel_params: tuple = ()):
    """A fresh ``(pol_state, ch_state, key)`` carry for
    :func:`make_schedule_chunk_runner` (:func:`_fresh_states`). The carry
    holds its own copy of ``key`` (chunks donate their carry)."""
    chan = make_channel(channel, sigmas, ch, **dict(channel_params))
    states = jax.jit(functools.partial(_fresh_states, chan, policy,
                                       int(sigmas.shape[0])))
    return (*states(key), jnp.array(key, copy=True))


def _schedule_rows(t_comm, power, n_sel, overflow, ids):
    """A chunk's output, ONE uint32 array: per round t_comm and power as
    their float32 bits, n_sel, overflow, then the packed ids."""
    u32 = jnp.uint32
    head = jnp.stack([jax.lax.bitcast_convert_type(t_comm, u32),
                      jax.lax.bitcast_convert_type(power, u32),
                      n_sel.astype(u32), overflow.astype(u32)], axis=1)
    return jnp.concatenate([head, ids.astype(u32)], axis=1)


class ScheduleChunks:
    """``run_chunk(carry, n_rounds) -> (carry, rows)``: ``n_rounds``
    scheduling rounds in one jitted call (``n_rounds`` static, the carry
    donated), as :func:`make_schedule_chunk_runner` builds it.

    ``rows`` is ONE (n_rounds, 4 + m_cap) uint32 array, so a chunk's
    results cross to the host in one transfer; :meth:`unpack` reads it on
    the host. The decision coefficients go to the device once, here. A
    chunk call records the ``fl.dispatch`` span, and each new chunk length
    counts an ``engine_compile_misses_total`` miss, as the scan engine's
    chunk runner does.
    """

    def __init__(self, scan_rounds, co: DecisionCoeffs):
        def chunk(carry, co, n_rounds):
            carry, outs = scan_rounds(carry, co, n_rounds)
            with jax.named_scope("fl.pack"):
                return carry, _schedule_rows(*outs)

        self._jit = jax.jit(chunk, static_argnames=("n_rounds",),
                            donate_argnums=(0,))
        self.co = jax.device_put(co)
        self._ei = EngineInstruments(obs_metrics.default_registry())

    def __call__(self, carry, n_rounds: int):
        self._ei.compiles.miss(("schedule_chunk", n_rounds),
                               entry="schedule_chunk", n_rounds=n_rounds)
        with span("fl.dispatch"):
            return self._jit(carry, self.co, n_rounds=n_rounds)

    def lower(self, carry, n_rounds: int):
        """The chunk program for ``n_rounds``, lowered (not run)."""
        return self._jit.lower(carry, self.co, n_rounds=n_rounds)

    def unpack(self, rows) -> dict:
        """A chunk's rows on the host: per round ``t_comm``, ``power``
        (float32), ``n_sel``, ``overflow`` (the selected clients past
        ``m_cap``) and ``ids`` (n_rounds, m_cap), the selected clients
        ascending, zero-filled past ``min(n_sel, m_cap)``. Adds the
        overflow to ``fl_schedule_overflow_total``."""
        rows = np.asarray(rows)
        out = dict(t_comm=rows[:, 0].view(np.float32),
                   power=rows[:, 1].view(np.float32),
                   n_sel=rows[:, 2].astype(np.int32),
                   overflow=rows[:, 3].astype(np.int32),
                   ids=rows[:, 4:].astype(np.int32))
        self._ei.schedule_overflow.inc(int(out["overflow"].sum()))
        return out


def _schedule_rounds(sigmas: jax.Array, scfg: SchedulerConfig,
                     ch: ChannelConfig, *, policy: str, m_avg: float,
                     channel: str, channel_params: tuple, solver: str,
                     client_shards: int, m_cap: int, devices):
    """``scan_rounds(carry, co, n_rounds) -> (carry, (t_comm, power, n_sel,
    overflow, ids))``, each stacked over the rounds: the one scheduling
    round body both runners scan (see :func:`make_schedule_chunk_runner`).
    The ids and the overflow are separate outputs, so a caller that drops
    them drops the pack with them."""
    n = int(sigmas.shape[0])
    fused = uses_fused(solver, policy)
    chan = make_channel(channel, sigmas, ch, **dict(channel_params))
    if client_shards:
        schedule = make_sharded_schedule(
            policy, channel, channel_params, scfg, ch, sigmas,
            n_shards=client_shards, m_cap=m_cap, m_avg=m_avg,
            devices=devices, fused=fused)

        def round_fn(pol_state, ch_state, k, co):
            k_ch, k_sel, _ = jax.random.split(k, 3)
            with jax.named_scope("fl.decision"):
                raw_ch = draw_channel_raw(channel, k_ch, n,
                                          dict(channel_params))
                raw_pol = draw_policy_raw(policy, k_sel, n)
                (t_comm, power, n_sel, ids, _, _, pol_state,
                 ch_state) = schedule(raw_ch, raw_pol, pol_state, ch_state,
                                      co)
            with jax.named_scope("fl.pack"):
                overflow = n_sel - jnp.minimum(n_sel, m_cap)
            return pol_state, ch_state, (t_comm, power, n_sel, overflow, ids)
    else:
        def round_fn(pol_state, ch_state, k, co):
            # the sequential reference IS the shared decision layer (the
            # same function the scan engine and the service run)
            step = make_policy(policy, scfg, ch, m_avg=m_avg,
                               coeffs=co.solve)
            decision = decision_step
            if fused:
                from repro.fl.decision import make_fused_decision
                decision = make_fused_decision(scfg, co)
            k_ch, k_sel, _ = jax.random.split(k, 3)
            with jax.named_scope("fl.decision"):
                gains, ch_state = channel_obs(chan.step, k_ch, ch_state)
                sel, q, p, t_comm, power, n_sel, pol_state = decision(
                    step, co.acct, k_sel, gains, pol_state)
            with jax.named_scope("fl.pack"):
                ids, _, overflow = pack_participants(sel, m_cap)
            return pol_state, ch_state, (t_comm, power, n_sel, overflow, ids)

    def scan_rounds(carry, co, n_rounds):
        def body(carry, _):
            pst, cst, k = carry
            k, kr = jax.random.split(k)
            pst, cst, out = round_fn(pst, cst, kr, co)
            return (pst, cst, k), out

        return jax.lax.scan(body, carry, None, length=n_rounds)

    return scan_rounds


def make_schedule_chunk_runner(sigmas: jax.Array, scfg: SchedulerConfig,
                               ch: ChannelConfig, *,
                               policy: str = "proposed", m_avg: float = 0.0,
                               channel: str = "rayleigh",
                               channel_params: tuple = (),
                               solver: str = "jnp", client_shards: int = 0,
                               m_cap: int = 32,
                               devices=None) -> ScheduleChunks:
    """The scheduling layer alone (no model training, no dataset), stepped:
    ``run_chunk(carry, n_rounds) -> (carry, rows)`` from a carry of
    :func:`init_schedule_carry`, so the Eq. 9 queues carry from one call
    to the next and a fleet is scheduled round after round.

    Each round's row holds its TDMA communication time, sum P q, the
    participation count, the selected clients that did not fit in
    ``m_cap`` slots, and the first ``m_cap`` selected client ids,
    ascending (:meth:`ScheduleChunks.unpack`).

    ``client_shards=0`` is the sequential reference: the SAME per-round key
    chain and the same blocked accounting reduce, driven through the
    registry channel/policy steps on one device — so sharded and sequential
    trajectories are comparable exactly (the accounting island must agree
    bit for bit; tests/test_client_sharded.py's massive leg checks this at
    N = 10^5).

    ``solver="pallas_fused"`` (with ``policy="proposed"``) routes the
    decision through the fused megakernel on both branches — the whole
    sequential decision in one kernel pass, or one pass per shard slice —
    bitwise-equal to the stitched paths, so the sequential-vs-sharded
    comparison above is unchanged.

    Device operations carry the named scopes ``fl.decision`` (channel
    observation and decision; in the sharded branch the merged index pack
    too) and ``fl.pack`` (the id pack and the output rows).
    """
    return ScheduleChunks(
        _schedule_rounds(sigmas, scfg, ch, policy=policy, m_avg=m_avg,
                         channel=channel, channel_params=channel_params,
                         solver=solver, client_shards=client_shards,
                         m_cap=m_cap, devices=devices),
        decision_coeffs(scfg, ch))


def make_schedule_runner(sigmas: jax.Array, scfg: SchedulerConfig,
                         ch: ChannelConfig, *, rounds: int,
                         policy: str = "proposed", m_avg: float = 0.0,
                         channel: str = "rayleigh",
                         channel_params: tuple = (), solver: str = "jnp",
                         client_shards: int = 0, m_cap: int = 32,
                         devices=None):
    """A whole scheduling-layer trajectory from fresh queues.

    ``runner(key) -> (t_comm, power, n_sel)``, each (rounds,):
    per-round TDMA communication time, sum P q, and participation count —
    the massive-N hot path alone, which is what ``bench_massive`` times and
    ``examples/massive_n.py`` demonstrates at N = 10^5..10^6. It is one
    jitted program: the carry of :func:`init_schedule_carry` and the rounds
    of :func:`make_schedule_chunk_runner` (see there for ``client_shards``,
    ``solver`` and ``m_cap``), without the ids, so the compiler drops the
    id pack.
    """
    scan_rounds = _schedule_rounds(
        sigmas, scfg, ch, policy=policy, m_avg=m_avg, channel=channel,
        channel_params=channel_params, solver=solver,
        client_shards=client_shards, m_cap=m_cap, devices=devices)
    chan = make_channel(channel, sigmas, ch, **dict(channel_params))
    n = int(sigmas.shape[0])
    co = jax.device_put(decision_coeffs(scfg, ch))

    @jax.jit
    def _runner(key, co):
        carry = (*_fresh_states(chan, policy, n, key), key)
        _, (t_comm, power, n_sel, _, _) = scan_rounds(carry, co, rounds)
        return t_comm, power, n_sel

    def runner(key):
        return _runner(key, co)

    return runner


# --------------------------------------------------------------------------
# The full client-sharded simulation round (drop-in for make_sim_round).
# --------------------------------------------------------------------------

def make_client_sharded_round(ds, sim, scfg: SchedulerConfig,
                              ch: ChannelConfig, sigmas: jax.Array,
                              coeffs: DecisionCoeffs = None):
    """The client-sharded ``sim_round`` for the scan engine.

    Same signature and carry layout as ``make_sim_round``'s product —
    ``sim_round(params, pol_state, ch_state, key) -> (params, pol_state,
    ch_state, t_comm, power, n_sel)`` — so ``run_config_chunks`` and the
    whole history machinery drive it unchanged. Scheduling runs on the
    ``'client'`` mesh; the <= m_cap merged participants then train exactly
    as the sequential engine trains them (same packed indices, same batch
    draws, same masked aggregate).

    ``sim.participant_shards >= 1`` COMPOSES both shardings on one shared
    2D ``('client', 'part')`` mesh (``fl/sharding.py::make_mesh2d``): the
    (N,)-client schedule shards over ``'client'`` (replicated across
    'part' columns), the packed participants' local SGD shards over
    ``'part'`` (replicated across 'client' rows, the Algorithm-1 line-7
    aggregate as a psum), and the all-gathered <= m_cap index pack is the
    only hand-off between the stages. Each stage's per-device program is
    identical to its 1D case, so the per-mesh numeric contract carries
    over: mesh (1, 1) stays bitwise-equal to ``run_simulation_scan`` and
    integer accounting stays exact on every mesh (tests/test_mesh2d.py).
    """
    from repro.fl.engine import resolve_wire_dtype
    from repro.fl.round import (local_sgd, make_sharded_round_update,
                                masked_aggregate, sample_batches)
    from repro.fl.sharding import make_mesh2d
    from repro.models.registry import make_model

    n = ds.n_clients
    spec = make_model(sim.model, ds, **dict(sim.model_params))
    wire = resolve_wire_dtype(sim.wire_dtype)
    co = coeffs if coeffs is not None else decision_coeffs(scfg, ch)
    mesh2d = None
    sharded_update = None
    if sim.participant_shards:
        mesh2d = make_mesh2d(sim.client_shards, sim.participant_shards)
        sharded_update = make_sharded_round_update(
            spec.loss_fn, sim.gamma, sim.local_steps, n,
            sim.participant_shards, aggregation=sim.aggregation,
            wire_dtype=wire, mesh=mesh2d)
    schedule = make_sharded_schedule(
        sim.policy, sim.channel, sim.channel_params, scfg, ch, sigmas,
        n_shards=sim.client_shards, m_cap=sim.m_cap, m_avg=sim.uniform_m,
        population=sim.population, mesh=mesh2d,
        fused=uses_fused(sim.solver, sim.policy))

    def sim_round(params, pol_state, ch_state, key):
        k_ch, k_sel, k_bat = jax.random.split(key, 3)
        raw_ch = draw_channel_raw(sim.channel, k_ch, n, sim.channel_params)
        raw_pol = draw_policy_raw(sim.policy, k_sel, n)
        if sim.population is not None:
            # churn/failure uniforms: fold_in side-channels of the ROUND
            # key, drawn full-shape outside the mesh — the same bits the
            # sequential population round consumes (mesh-invariant)
            from repro.fl.population import draw_churn_raw, draw_fail_raw
            raw_pop = (draw_churn_raw(key, n), draw_fail_raw(key, n))
            (t_comm, power, n_sel, sel_idx, sel_valid, q_sel, pol_state,
             ch_state) = schedule(raw_ch, raw_pol, raw_pop, pol_state,
                                  ch_state, co)
        else:
            (t_comm, power, n_sel, sel_idx, sel_valid, q_sel, pol_state,
             ch_state) = schedule(raw_ch, raw_pol, pol_state, ch_state, co)
        imgs, labs = sample_batches(k_bat, ds.client_images,
                                    ds.client_labels, sel_idx, sim.m_cap,
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
        return new_params, pol_state, ch_state, t_comm, power, n_sel

    return sim_round
