"""Fused decision megakernel: bitwise parity with the stitched decision,
block-boundary edges, mask/pad/failed-lane hygiene, and every consumer path.

The binding contract (ISSUE 7 / docs/paper_map.md): with
``solver="pallas_fused"`` every decision the repo takes — scan engine,
population round, client-sharded runner, bucket-batched service — is
BITWISE-equal to the stitched ``decision_step`` composition, because the
kernel reuses the jnp oracle's traced ops on the same runtime operand
vector (the operand contract). Policies without a fused kernel fall back
to the stitched path, which must pass through unperturbed — the 6-policy
x 4-channel sweep pins exactly that.

Runs in interpret mode on CPU CI; the ``pallas`` marker re-runs the file
on the nightly kernel-parity leg.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.core import ChannelConfig, SchedulerConfig, make_policy
from repro.core.policies import POLICIES, init_policy_state
from repro.fl.decision import (SOLVERS, decision_coeffs, decision_step,
                               make_fused_decision, uses_fused)
from repro.kernels.decision_fused import (N_DECISION_OPS, decision_fused,
                                          decision_fused_batched,
                                          pack_decision_operands)

pytestmark = pytest.mark.pallas  # nightly kernel-parity leg re-runs these

BLOCK = 128  # kernel default is 1024; small blocks make edges cheap
EDGE_SIZES = [1, BLOCK - 1, BLOCK, BLOCK + 1, 3 * BLOCK + 17]

CH = ChannelConfig(n_clients=100)
CFG = SchedulerConfig(n_clients=100, model_bits=32 * 555178.0, lam=10.0,
                      V=1000.0)


def _states(key, n):
    gains = jnp.exp(jax.random.normal(key, (n,)) * 2.0).astype(jnp.float32)
    z = (jnp.abs(jax.random.normal(jax.random.fold_in(key, 1), (n,)))
         * 50.0).astype(jnp.float32)
    return gains, z


def _boundary_states(n):
    """Branch-boundary solver states: gains at the modulation clip bounds,
    Z = 0 exactly (the Z-floor branch), huge queues (the P = Pmax
    boundary branch)."""
    lo, hi = CH.gain_bounds()
    reps = -(-n // 6)
    gains = jnp.tile(jnp.array([lo, hi, 1.0, 1e-3, 1e3, 37.0],
                               jnp.float32), reps)[:n]
    z = jnp.tile(jnp.array([0.0, 0.0, 1e4, 5.0, 0.0, 1e-6], jnp.float32),
                 reps)[:n]
    return gains, z


def _block_boundary_mask(n, block=BLOCK):
    """All-active except sentinel lanes at every block-1/block/block+1
    boundary plus the last lane."""
    off = [b * block + d for b in range(1, n // block + 1)
           for d in (-1, 0, 1)] + [n - 1]
    return jnp.ones((n,), bool).at[jnp.array(
        [i for i in off if i < n])].set(False)


def _stitched(co, key, gains, st, active=None, cfg=CFG):
    step = make_policy("proposed", cfg, CH, coeffs=co.solve)
    if active is None:
        return decision_step(step, co.acct, key, gains, st)
    n_act = jnp.sum(active.astype(jnp.int32))
    mstep = lambda k, g, s: step(k, g, s, active, n_act)  # noqa: E731
    return decision_step(mstep, co.acct, key, gains, st, valid=active)


def _fused(co, key, gains, st, active=None, cfg=CFG, block=BLOCK):
    fd = make_fused_decision(cfg, co, block=block)
    return fd(None, None, key, gains, st, valid=active)


# module-level jits: every test below that varies only the coefficient
# operands (lambda, V) shares one compiled program per shape
_STITCHED = jax.jit(_stitched)
_FUSED = jax.jit(_fused)


def _assert_decisions_equal(a, b):
    names = ("sel", "q", "p", "t_comm", "power", "n_sel", "z", "aux", "t")
    va = list(a[:6]) + [a[6].z, a[6].aux, a[6].t]
    vb = list(b[:6]) + [b[6].z, b[6].aux, b[6].t]
    for nm, x, y in zip(names, va, vb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=f"field {nm} diverged")


# ---------------------------------------------------------------------------
# Kernel-level parity + edges (the tentpole's bitwise contract, directly).
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", EDGE_SIZES)
def test_edge_sizes_bitwise_parity(n):
    """Every block-boundary-straddling size: fused == stitched, bitwise,
    on every output (sel/q/p/t_comm/power/n_sel/state)."""
    gains, z = _states(jax.random.PRNGKey(n), n)
    st = init_policy_state("proposed", n)._replace(z=z)
    key = jax.random.PRNGKey(42)
    co = decision_coeffs(CFG, CH)
    _assert_decisions_equal(jax.jit(_stitched)(co, key, gains, st),
                            jax.jit(_fused)(co, key, gains, st))


@pytest.mark.parametrize("n", EDGE_SIZES)
def test_branch_boundary_states_bitwise_parity(n):
    """Branch-boundary solver states at every pad geometry stay finite and
    bitwise-equal — pad lanes (gains=1, z=0, u=2) share the Z-floor branch
    and may not emit NaN/inf that could leak into real lanes."""
    gains, z = _boundary_states(n)
    st = init_policy_state("proposed", n)._replace(z=z)
    key = jax.random.PRNGKey(7)
    co = decision_coeffs(CFG, CH)
    a = jax.jit(_stitched)(co, key, gains, st)
    b = jax.jit(_fused)(co, key, gains, st)
    _assert_decisions_equal(a, b)
    for x in (b[1], b[2], b[3], b[4], b[6].z):
        assert np.isfinite(np.asarray(x)).all()
    assert (np.asarray(b[6].z) >= 0.0).all()


def test_masked_block_boundary_lanes(n=3 * BLOCK + 17):
    """Inactive sentinel lanes sitting exactly on kernel block boundaries,
    with branch-boundary states: never selected, q = 0 exactly, excluded
    from the power accounting, Z still drains — and the whole masked
    decision stays bitwise-equal to the stitched masked policy."""
    gains, z = _boundary_states(n)
    active = _block_boundary_mask(n)
    st = init_policy_state("proposed", n)._replace(z=z)
    key = jax.random.PRNGKey(3)
    co = decision_coeffs(CFG, CH)
    a = jax.jit(_stitched)(co, key, gains, st, active)
    b = jax.jit(_fused)(co, key, gains, st, active)
    _assert_decisions_equal(a, b)
    sel, q = np.asarray(b[0]), np.asarray(b[1])
    inactive = ~np.asarray(active)
    assert not sel[inactive].any()
    np.testing.assert_array_equal(q[inactive], 0.0)
    # inactive lanes still drain: Z' = max(Z + P*0 - Pbar, 0), f32 exact
    z_exp = np.maximum(np.asarray(z) - np.float32(CH.p_bar),
                       np.float32(0.0))[inactive]
    np.testing.assert_array_equal(np.asarray(b[6].z)[inactive], z_exp)


@pytest.mark.parametrize("solver", ["jnp", "pallas_fused"])
def test_masked_step_inactive_lanes_at_block_boundaries(solver):
    """Inactive sentinel lanes sitting exactly on kernel block boundaries,
    with branch-boundary states, are never selected and take q = 0 exactly
    — on the stitched decision and the fused kernel alike — and no lane
    (active, inactive, or kernel pad) emits NaN/inf."""
    n = 3 * BLOCK + 17
    cfg = SchedulerConfig(n_clients=n, model_bits=32 * 555178.0)
    decide = _fused if uses_fused(solver, "proposed") else _stitched
    gains, z = _boundary_states(n)
    active = _block_boundary_mask(n)
    st0 = init_policy_state("proposed", n)._replace(z=z)
    sel, q, p, _, _, _, st1 = decide(decision_coeffs(cfg, CH),
                                     jax.random.PRNGKey(0), gains, st0,
                                     active, cfg=cfg)
    sel, q, p = np.asarray(sel), np.asarray(q), np.asarray(p)
    inactive = ~np.asarray(active)
    assert not sel[inactive].any()
    np.testing.assert_array_equal(q[inactive], 0.0)
    assert np.isfinite(q).all() and np.isfinite(p).all()
    assert np.isfinite(np.asarray(st1.z)).all()
    assert (np.asarray(st1.z) >= 0.0).all()


def _assert_fused_matches_stitched(lam, v, n, seed):
    """Fused == stitched, bitwise on every output, for one (lambda, V) at
    one size: the coefficients change only runtime operands, so the two
    programs are the module-level ones."""
    cfg = SchedulerConfig(n_clients=100, model_bits=32 * 555178.0, lam=lam,
                          V=v)
    co = decision_coeffs(cfg, CH)
    gains, z = _states(jax.random.PRNGKey(seed), n)
    st0 = init_policy_state("proposed", n)._replace(z=z)
    key = jax.random.fold_in(jax.random.PRNGKey(seed), 2)
    b = _FUSED(co, key, gains, st0)
    _assert_decisions_equal(_STITCHED(co, key, gains, st0), b)
    for x in (b[1], b[2], b[6].z):
        assert np.isfinite(np.asarray(x)).all()


@settings(deadline=None, max_examples=10)
@given(st.integers(min_value=0, max_value=2 ** 31 - 1),    # PRNG seed
       st.floats(min_value=0.1, max_value=1e3),            # lambda
       st.floats(min_value=1.0, max_value=1e5))            # V
def test_fused_stitched_parity_property(seed, lam, v):
    """Property form: random (lambda, V) x random states at a
    block-straddling size keep the fused kernel bitwise on the stitched
    decision."""
    _assert_fused_matches_stitched(lam, v, BLOCK + 1, seed)


_SWEEP_RNG = np.random.default_rng(7)
SWEEP_CONFIGS = [(float(10 ** _SWEEP_RNG.uniform(-1, 3)),
                  float(10 ** _SWEEP_RNG.uniform(0, 5)),
                  int(_SWEEP_RNG.integers(0, 2 ** 31)))
                 for _ in range(6)]


@pytest.mark.parametrize("lam,v,seed", SWEEP_CONFIGS,
                         ids=[f"cfg{i}" for i in range(len(SWEEP_CONFIGS))])
def test_fused_stitched_parity_deterministic_sweep(lam, v, seed):
    """Fixed-seed fallback for the property above (hypothesis is
    optional): one (lambda, V) per case, at four sizes around a block
    boundary."""
    for i, n in enumerate((1, BLOCK - 1, BLOCK, BLOCK + 1)):
        _assert_fused_matches_stitched(lam, v, n, seed + i)


def test_failed_lanes_stay_charged():
    """Eq. 9 charges every SELECTED lane, delivered or not: the kernel's
    Z-update takes no failure input, so a selected-but-failed lane carries
    exactly the same Z' (and airtime contribution) as a delivered twin."""
    from repro.fl.population import failure_split, population_config
    n = 2 * BLOCK
    gains, z = _states(jax.random.PRNGKey(5), n)
    st = init_policy_state("proposed", n)._replace(z=z)
    co = decision_coeffs(CFG, CH)
    sel, q, p, t_comm, power, n_sel, st1 = jax.jit(_fused)(
        co, jax.random.PRNGKey(11), gains, st)
    pcfg = population_config((("p_fail", 0.5),))
    fail_raw = jax.random.uniform(jax.random.PRNGKey(12), (n,))
    delivered, failed = failure_split(fail_raw, sel, pcfg)
    assert bool(jnp.any(failed)), "scenario must actually fail some lanes"
    # Z' is a function of (z, q, p) alone — identical whether the lane
    # delivered or timed out (tolerance: XLA contracts z + p*q into an fma)
    z_exp = np.maximum(np.asarray(z) + np.asarray(p) * np.asarray(q)
                       - np.float32(CH.p_bar), np.float32(0.0))
    np.testing.assert_allclose(np.asarray(st1.z), z_exp, rtol=1e-6)
    # and the airtime/participation accounting counted the failed lanes
    assert int(n_sel) == int(jnp.sum(delivered) + jnp.sum(failed))


def test_block_override_bitwise_invariant():
    """Tiling is a layout choice: per-lane results must not depend on it,
    bit for bit (the engine runs block=1024, tests run 128)."""
    n = 3 * BLOCK + 17
    gains, z = _states(jax.random.PRNGKey(9), n)
    u = jax.random.uniform(jax.random.PRNGKey(10), (n,))
    co = decision_coeffs(CFG, CH)
    ops = pack_decision_operands(co.solve, co.acct)
    outs = [decision_fused(gains, z, u, ops, block=b)
            for b in (64, BLOCK, 1024)]
    for other in outs[1:]:
        for x, y in zip(outs[0], other):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_operand_vector_layout():
    """The (14,) operand pack is positional on SolveCoeffs order + the
    accounting triple; a silent reorder would break every consumer."""
    co = decision_coeffs(CFG, CH)
    ops = np.asarray(pack_decision_operands(co.solve, co.acct))
    assert ops.shape == (N_DECISION_OPS,)
    np.testing.assert_array_equal(ops[:11], np.asarray(list(co.solve),
                                                       np.float32))
    np.testing.assert_array_equal(
        ops[11:], np.asarray([co.acct.ell, co.acct.bw, co.acct.n0],
                             np.float32))


def test_rejects_degenerate_shapes():
    gains, z = _states(jax.random.PRNGKey(0), 4)
    u = jax.random.uniform(jax.random.PRNGKey(1), (4,))
    ops = pack_decision_operands(*decision_coeffs(CFG, CH))
    with pytest.raises(ValueError, match="block"):
        decision_fused(gains, z, u, ops, block=0)
    with pytest.raises(ValueError, match="at least one"):
        decision_fused(jnp.zeros((0,)), jnp.zeros((0,)), jnp.zeros((0,)),
                       ops)
    with pytest.raises(ValueError, match="non-empty"):
        decision_fused_batched(jnp.zeros((0, 4)), jnp.zeros((0, 4)),
                               jnp.zeros((0, 4)),
                               jnp.zeros((0, N_DECISION_OPS)))


# ---------------------------------------------------------------------------
# Engine dispatch: all 6 policies x 4 channels, jnp vs pallas_fused.
# ---------------------------------------------------------------------------

CHANNELS = [("rayleigh", ()), ("rician", (("k_factor", 3.0),)),
            ("lognormal", ()), ("gauss_markov", (("rho", 0.8),))]


@pytest.mark.parametrize("policy", sorted(POLICIES))
@pytest.mark.parametrize("channel,cparams", CHANNELS)
def test_all_policies_x_channels_bitwise(policy, channel, cparams):
    """solver="pallas_fused" vs "jnp" across the full policy x channel
    registry: the proposed rows exercise the kernel; every other policy
    must pass through the dispatch unperturbed (same trajectory, bitwise).
    Scheduling-only (no training) keeps the 24-cell sweep cheap."""
    from repro.fl.client_shard import make_schedule_runner
    n = BLOCK + 33
    scfg = dataclasses.replace(CFG, n_clients=n)
    sigmas = jnp.ones((n,), jnp.float32)
    m_avg = 0.0 if policy == "proposed" else 6.0
    key = jax.random.PRNGKey(17)
    outs = [make_schedule_runner(sigmas, scfg, CH, rounds=3, policy=policy,
                                 m_avg=m_avg, channel=channel,
                                 channel_params=cparams, solver=s)(key)
            for s in ("jnp", "pallas_fused")]
    for x, y in zip(*outs):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_masked_population_decision_across_channels():
    """Masked-population parity on gains from each channel model: the
    fused ``valid`` doubles as the activity mask (q -> 0 pre-selection AND
    the pq accounting mask), bitwise against the stitched masked step."""
    from repro.core.channel import make_channel
    n = 2 * BLOCK + 9
    co = decision_coeffs(CFG, CH)
    active = _block_boundary_mask(n)
    for i, (channel, cparams) in enumerate(CHANNELS):
        chan = make_channel(channel, jnp.ones((n,), jnp.float32), CH,
                            **dict(cparams))
        cst = chan.init(jax.random.PRNGKey(100 + i))
        gains, _ = chan.step(jax.random.PRNGKey(200 + i), cst)
        z = (jnp.abs(jax.random.normal(jax.random.PRNGKey(300 + i), (n,)))
             * 50.0).astype(jnp.float32)
        st = init_policy_state("proposed", n)._replace(z=z)
        key = jax.random.PRNGKey(400 + i)
        _assert_decisions_equal(
            jax.jit(_stitched)(co, key, gains, st, active),
            jax.jit(_fused)(co, key, gains, st, active))


# ---------------------------------------------------------------------------
# The client-sharded and service consumers.
# ---------------------------------------------------------------------------

def test_sharded_mesh1_bitwise():
    """client_shards=1 fused == client_shards=1 stitched, bitwise; and
    against the sequential jnp runner, n_sel exact with float accounting
    to ~1 ulp — the contract every other mesh carries. (XLA:CPU emits the
    same accounting ops one ulp apart inside the scanned shard_map
    program: the stitched sharded runner drifts the same way.)"""
    from repro.fl.client_shard import make_schedule_runner
    n = 401
    scfg = dataclasses.replace(CFG, n_clients=n)
    sigmas = jnp.ones((n,), jnp.float32)
    key = jax.random.PRNGKey(21)
    seq = make_schedule_runner(sigmas, scfg, CH, rounds=4, solver="jnp")(key)
    ref = make_schedule_runner(sigmas, scfg, CH, rounds=4, solver="jnp",
                               client_shards=1)(key)
    out = make_schedule_runner(sigmas, scfg, CH, rounds=4,
                               solver="pallas_fused", client_shards=1)(key)
    for x, y in zip(ref, out):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    t_comm, power, n_sel = (np.asarray(x) for x in out)
    np.testing.assert_array_equal(n_sel, np.asarray(seq[2]))
    np.testing.assert_allclose(t_comm, np.asarray(seq[0]), rtol=3e-7, atol=0)
    np.testing.assert_allclose(power, np.asarray(seq[1]), rtol=3e-7, atol=0)


def test_sharded_rejects_fused_baselines():
    from repro.fl.client_shard import make_sharded_schedule
    n = 64
    scfg = dataclasses.replace(CFG, n_clients=n)
    with pytest.raises(ValueError, match="fused"):
        make_sharded_schedule("uniform", "rayleigh", (), scfg, CH,
                              jnp.ones((n,), jnp.float32), n_shards=1,
                              m_cap=8, m_avg=6.0, fused=True)


def test_service_heterogeneous_bitwise():
    """The bucket-batched fused service: heterogeneous tenants (different
    N, different scalars — impossible for solver='pallas') across repeated
    flushes, bitwise against the stitched jnp service, including the
    bucket-pad lanes beyond each tenant's real N."""
    from repro.service.batching import SchedulerService

    def run(solver):
        svc = SchedulerService(solver=solver)
        cfg_a = dataclasses.replace(CFG, n_clients=100)
        cfg_b = SchedulerConfig(n_clients=120, model_bits=32 * 3000.0,
                                lam=5.0, V=500.0)
        svc.add_tenant("a", cfg_a, ChannelConfig(n_clients=100))
        svc.add_tenant("b", cfg_b, ChannelConfig(n_clients=120))
        out = []
        for t in range(3):
            for name, n in (("a", 100), ("b", 120)):
                g = np.asarray(jnp.exp(jax.random.normal(
                    jax.random.PRNGKey(50 + 10 * t + n), (n,)) * 1.5),
                    np.float32)
                svc.submit(name, g, key=jax.random.PRNGKey(60 + 10 * t + n))
            out.append(svc.flush())
        return out

    ref, fus = run("jnp"), run("pallas_fused")
    for f1, f2 in zip(ref, fus):
        assert f1.keys() == f2.keys()
        for t in f1:
            for fld in f1[t]._fields:
                np.testing.assert_array_equal(
                    np.asarray(getattr(f1[t], fld)),
                    np.asarray(getattr(f2[t], fld)),
                    err_msg=f"tenant {t} field {fld}")


def test_service_rejects_unknown_solver_and_fused_baseline():
    from repro.service.batching import SchedulerService
    from repro.service.step import make_bucket_step
    with pytest.raises(ValueError, match="solver"):
        SchedulerService(solver="nope")
    with pytest.raises(ValueError, match="fused"):
        make_bucket_step("uniform", 64, 64, True, fused=True)
    # non-proposed buckets under a fused service fall back to stitched jnp
    svc = SchedulerService(solver="pallas_fused")
    n = 32
    scfg = dataclasses.replace(CFG, n_clients=n)
    svc.add_tenant("u", scfg, ChannelConfig(n_clients=n), policy="uniform",
                   m_avg=4.0)
    g = np.full((n,), 1.0, np.float32)
    svc.submit("u", g, key=jax.random.PRNGKey(0))
    out = svc.flush()["u"]
    assert out.sel.shape == (n,)


# ---------------------------------------------------------------------------
# The one decision path: uses_fused picks the kernel, and nothing else does.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("solver,policy,fused", [
    ("jnp", "proposed", False), ("jnp", "uniform", False),
    ("pallas_fused", "proposed", True), ("pallas_fused", "uniform", False),
    ("pallas_fused", "greedy_channel", False)])
def test_uses_fused_truth_table(solver, policy, fused):
    assert solver in SOLVERS
    assert uses_fused(solver, policy) is fused


@pytest.mark.parametrize("solver", ["pallas", "cuda", ""])
def test_uses_fused_rejects_other_names(solver):
    with pytest.raises(ValueError, match="'jnp', 'pallas_fused'"):
        uses_fused(solver, "proposed")


def _holds_fused_kernel(text):
    """The traced program holds a ``pallas_call`` of the fused kernel
    (1D or bucket-batched). On the CPU the kernel runs in interpret mode,
    whose lowered text no longer names it; the jaxpr still does."""
    return re.search(r"\bname=decision_fused(_batched)?\b", text) is not None


@pytest.fixture(scope="module")
def tiny_fl():
    from repro.data.synthetic import make_cifar10_like
    from repro.models.registry import make_model
    n = 24
    ds = make_cifar10_like(jax.random.PRNGKey(0), n_clients=n,
                           per_client=16, n_test=32, h=8, w=8)
    params = make_model("mlp", ds).init_fn(jax.random.PRNGKey(1))
    return (ds, params, dataclasses.replace(CFG, n_clients=n),
            ChannelConfig(n_clients=n), jnp.ones((n,), jnp.float32))


def _engine_text(tiny_fl, solver, population=None):
    from repro.fl.engine import SimConfig, init_carry, make_chunk_runner
    ds, params, scfg, ch, sig = tiny_fl
    sim = SimConfig(rounds=2, eval_every=1, m_cap=4, batch=4, local_steps=1,
                    eval_size=32, model="mlp", solver=solver,
                    population=population)
    run = make_chunk_runner(ds, sim, scfg, ch, sig)
    carry = init_carry(jax.random.PRNGKey(2), params, scfg, sim, sig, ch)
    return str(jax.make_jaxpr(lambda c: run(c, 1))(carry))


def _schedule_text(tiny_fl, solver):
    from repro.fl.client_shard import (init_schedule_carry,
                                       make_schedule_chunk_runner)
    _, _, scfg, ch, sig = tiny_fl
    run = make_schedule_chunk_runner(sig, scfg, ch, solver=solver, m_cap=4)
    carry = init_schedule_carry(jax.random.PRNGKey(3), sig, ch)
    return str(jax.make_jaxpr(lambda c: run(c, 2))(carry))


def _service_text(tiny_fl, solver, monkeypatch):
    from repro.service import batching
    _, _, scfg, ch, _ = tiny_fl
    texts = []
    real = batching.make_bucket_step

    def make(*a, **k):
        step = real(*a, **k)

        def traced(*args):
            texts.append(str(jax.make_jaxpr(step)(*args)))
            return step(*args)
        return traced
    monkeypatch.setattr(batching, "make_bucket_step", make)
    svc = batching.SchedulerService(solver=solver)
    svc.add_tenant("t", scfg, ch)
    svc.submit("t", np.ones(scfg.n_clients, np.float32),
               key=jax.random.PRNGKey(4))
    svc.flush()
    assert len(texts) == 1
    return texts[0]


@pytest.mark.parametrize("solver", ["jnp", "pallas_fused"])
@pytest.mark.parametrize("path", ["engine", "population", "schedule",
                                  "service"])
def test_program_runs_fused_kernel_iff_uses_fused(tiny_fl, monkeypatch,
                                                  path, solver):
    """The program each consumer builds for ``proposed`` holds the fused
    kernel exactly when ``uses_fused`` says so: the engine round, the
    population round, the scheduling chunk runner and the service's
    bucket step."""
    if path == "engine":
        text = _engine_text(tiny_fl, solver)
    elif path == "population":
        text = _engine_text(tiny_fl, solver, population=())
    elif path == "schedule":
        text = _schedule_text(tiny_fl, solver)
    else:
        text = _service_text(tiny_fl, solver, monkeypatch)
    assert _holds_fused_kernel(text) is uses_fused(solver, "proposed")
