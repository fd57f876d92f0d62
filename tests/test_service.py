"""Multi-tenant scheduler service: the bitwise-parity contract + hygiene.

The binding contract (repro/service): for a single tenant fed the gains
stream that ``run_simulation_scan`` would draw, the served per-round
decisions (sel, q, P) and accounting (t_comm, power, n_sel) are
BITWISE-equal to the engine's — the service is the engine's scheduling
layer (``repro/fl/decision.py``) refactored for online use. That rests on
the operand contract (repro/core/scheduler.py): both sides run the
coefficient bundle through a jit boundary as runtime operands, which is
bit-stable across array shapes, bucket padding, and vmap batching.

Also pinned here: bucket-padding hygiene (pad lanes and co-tenants never
alter a tenant's bits), donation safety + snapshot/restore mid-stream,
and bit-exact replay of a logged multi-tenant session (including through
the npz save/load round trip).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (ChannelConfig, SchedulerConfig, heterogeneous_sigmas,
                        init_policy_state, make_channel, make_policy)
from repro.core.policies import POLICY_DRAWS
from repro.fl.decision import channel_obs, decision_coeffs, decision_step
from repro.fl.engine import (CHANNEL_INIT_TAG, SimConfig, eval_rounds,
                             run_simulation_scan)
from repro.service import SchedulerService

N = 40
ROUNDS = 13
EVAL_EVERY = 5


def _configs(n=N, **kw):
    scfg = SchedulerConfig(n_clients=n, model_bits=32 * 50000.0,
                           **{k: v for k, v in kw.items()
                              if k in ("lam", "V", "q_floor")})
    ch = ChannelConfig(n_clients=n,
                       **{k: v for k, v in kw.items()
                          if k in ("p_max", "p_bar", "noise_power")})
    return scfg, ch


def _engine_stream(key, scfg, ch, sigmas, rounds, policy="proposed"):
    """The (gains, raw) stream run_simulation_scan would consume, plus the
    reference decision trajectory, computed by the SAME operand-driven
    decision layer the engine scans (repro/fl/decision.py)."""
    n = scfg.n_clients
    channel = make_channel("rayleigh", sigmas, ch)
    co_host = decision_coeffs(scfg, ch)

    @jax.jit
    def ref_round(pol_state, ch_state, k, co):
        step = make_policy(policy, scfg, ch, m_avg=5.0, coeffs=co.solve)
        k_ch, k_sel, _ = jax.random.split(k, 3)
        gains, ch_state = channel_obs(channel.step, k_ch, ch_state)
        sel, q, p, t_comm, power, n_sel, pol_state = decision_step(
            step, co.acct, k_sel, gains, pol_state)
        return (gains, sel, q, p, t_comm, power, n_sel, pol_state,
                ch_state)

    pol = init_policy_state(policy, n)
    cst = channel.init(jax.random.fold_in(key, CHANNEL_INIT_TAG))
    out = []
    for _ in range(rounds):
        key, k = jax.random.split(key)
        _, k_sel, _ = jax.random.split(k, 3)
        gains, sel, q, p, t_comm, power, n_sel, pol, cst = ref_round(
            pol, cst, k, co_host)
        raw = POLICY_DRAWS[policy](k_sel, n)
        out.append(dict(gains=np.asarray(gains), raw=raw,
                        sel=np.asarray(sel), q=np.asarray(q),
                        p=np.asarray(p), t_comm=np.asarray(t_comm),
                        power=np.asarray(power), n_sel=int(n_sel)))
    return out


def _drive_service(svc, name, stream):
    decisions = []
    for r in stream:
        svc.submit(name, r["gains"], raw=r["raw"])
        decisions.append(svc.flush()[name])
    return decisions


def _assert_decisions_equal(got, want, msg=""):
    np.testing.assert_array_equal(got.sel, want["sel"], err_msg=f"sel {msg}")
    np.testing.assert_array_equal(got.q, want["q"], err_msg=f"q {msg}")
    np.testing.assert_array_equal(got.p, want["p"], err_msg=f"p {msg}")
    np.testing.assert_array_equal(got.t_comm, want["t_comm"],
                                  err_msg=f"t_comm {msg}")
    np.testing.assert_array_equal(got.power, want["power"],
                                  err_msg=f"power {msg}")
    assert int(got.n_sel) == want["n_sel"], f"n_sel {msg}"


# --------------------------------------------------------------------------
# The binding contract: single tenant == run_simulation_scan, bitwise.
# --------------------------------------------------------------------------

@pytest.mark.parametrize("policy", ["proposed", "uniform", "greedy_channel"])
def test_single_tenant_decisions_bitwise_vs_engine(policy):
    scfg, ch = _configs()
    sig = heterogeneous_sigmas(N)
    key = jax.random.PRNGKey(2)
    stream = _engine_stream(key, scfg, ch, sig, ROUNDS, policy=policy)

    svc = SchedulerService()
    svc.add_tenant("t0", scfg, ch, policy=policy,
                   m_avg=0.0 if policy == "proposed" else 5.0)
    decisions = _drive_service(svc, "t0", stream)
    for r, (got, want) in enumerate(zip(decisions, stream)):
        _assert_decisions_equal(got, want, msg=f"round {r} ({policy})")


def test_single_tenant_accounting_bitwise_vs_scan_history():
    """The served accounting, f32-accumulated exactly as the scan carry
    accumulates it, reproduces run_simulation_scan's history bit for bit
    — the service IS the engine's scheduling layer."""
    from repro.data.synthetic import make_cifar10_like
    from repro.models.registry import make_model

    scfg, ch = _configs()
    sig = heterogeneous_sigmas(N)
    ds = make_cifar10_like(jax.random.PRNGKey(0), n_clients=N,
                           per_client=32, n_test=128, h=8, w=8)
    params = make_model("mlp", ds).init_fn(jax.random.PRNGKey(1))
    sim = SimConfig(rounds=ROUNDS, eval_every=EVAL_EVERY, m_cap=5, batch=4,
                    local_steps=1, eval_size=128, model="mlp")
    key = jax.random.PRNGKey(2)
    hist = run_simulation_scan(key, params, ds, sim, scfg, ch, sig)

    stream = _engine_stream(key, scfg, ch, sig, ROUNDS)
    svc = SchedulerService()
    svc.add_tenant("t0", scfg, ch)
    decisions = _drive_service(svc, "t0", stream)

    # f32 running sums, exactly as the scan carry adds them
    t_cum = np.float32(0.0)
    p_cum = np.float32(0.0)
    comm, pcum, nsel = [], [], []
    for d in decisions:
        t_cum = np.float32(t_cum + d.t_comm)
        p_cum = np.float32(p_cum + d.power)
        comm.append(t_cum)
        pcum.append(p_cum)
        nsel.append(int(d.n_sel))
    ev = eval_rounds(ROUNDS, EVAL_EVERY)
    np.testing.assert_array_equal(
        hist["comm_time"], np.asarray([comm[r] for r in ev], np.float64))
    np.testing.assert_array_equal(
        hist["n_selected"], np.asarray([nsel[r] for r in ev]))
    want_avg = (np.asarray([pcum[r] for r in ev]).astype(np.float64)
                / (np.asarray(ev) + 1) / N)
    np.testing.assert_array_equal(hist["avg_power"], want_avg)


# --------------------------------------------------------------------------
# Bucket padding hygiene: co-tenants and pad lanes never alter bits.
# --------------------------------------------------------------------------

def test_bucket_mix_never_alters_a_tenants_bits():
    """One tenant served alone vs served inside a full multi-tenant,
    multi-bucket stream (odd Ns, shared buckets, mixed policies):
    identical bits round for round."""
    scfg, ch = _configs()
    sig = heterogeneous_sigmas(N)
    stream = _engine_stream(jax.random.PRNGKey(2), scfg, ch, sig, 6)

    svc_solo = SchedulerService()
    svc_solo.add_tenant("t0", scfg, ch)
    solo = _drive_service(svc_solo, "t0", stream)

    svc_mix = SchedulerService()
    svc_mix.add_tenant("t0", scfg, ch)
    others = []
    rng = np.random.default_rng(0)
    for i, (n_o, policy, m_avg) in enumerate(
            [(40, "proposed", 0.0),      # same bucket as t0
             (63, "proposed", 0.0),      # same bucket, different N
             (21, "uniform", 4.0),       # other policy bucket
             (97, "greedy_channel", 3.0),
             (7, "proposed", 0.0)]):
        nm = f"o{i}"
        s_o = SchedulerConfig(n_clients=n_o,
                              model_bits=float(rng.uniform(1e5, 1e7)),
                              lam=float(rng.uniform(0.5, 30)),
                              V=float(rng.uniform(10, 1e4)))
        c_o = ChannelConfig(n_clients=n_o,
                            p_max=float(rng.uniform(20, 150)))
        svc_mix.add_tenant(nm, s_o, c_o, policy=policy, m_avg=m_avg)
        others.append((nm, s_o, c_o, policy))
    mixed = []
    for r, entry in enumerate(stream):
        svc_mix.submit("t0", entry["gains"], raw=entry["raw"])
        for j, (nm, s_o, c_o, policy) in enumerate(others):
            k = jax.random.fold_in(jax.random.PRNGKey(77), r * 31 + j)
            gains = np.abs(np.asarray(
                jax.random.normal(k, (s_o.n_clients,)))) + 0.01
            svc_mix.submit(nm, gains, key=jax.random.fold_in(k, 5))
        mixed.append(svc_mix.flush()["t0"])
    for r, (a, b) in enumerate(zip(solo, mixed)):
        np.testing.assert_array_equal(a.sel, b.sel, err_msg=f"round {r}")
        np.testing.assert_array_equal(a.q, b.q, err_msg=f"round {r}")
        np.testing.assert_array_equal(a.p, b.p, err_msg=f"round {r}")
        np.testing.assert_array_equal(a.t_comm, b.t_comm,
                                      err_msg=f"round {r}")
        np.testing.assert_array_equal(a.power, b.power,
                                      err_msg=f"round {r}")


def test_pad_rows_and_lanes_stay_finite_and_dead():
    """Sentinel batch rows and pad lanes must neither leak NaN/inf into
    responses nor ever mark a pad lane selected."""
    scfg, ch = _configs(n=21)   # odd N: 11 pad lanes in a 32-wide bucket
    svc = SchedulerService()
    svc.add_tenant("odd", scfg, ch)
    key = jax.random.PRNGKey(3)
    for r in range(4):
        k = jax.random.fold_in(key, r)
        gains = np.abs(np.asarray(jax.random.normal(k, (21,)))) + 0.01
        svc.submit("odd", gains, key=jax.random.fold_in(k, 9))
        d = svc.flush()["odd"]
        assert d.sel.shape == (21,) and d.q.shape == (21,)
        assert np.all(np.isfinite(d.q)) and np.all(np.isfinite(d.p))
        assert np.isfinite(d.t_comm) and np.isfinite(d.power)
        assert 1 <= int(d.n_sel) <= 21
    st = svc.tenant_state("odd")
    assert st.z.shape == (21,) and np.all(np.isfinite(st.z))
    assert int(st.t) == 4


# --------------------------------------------------------------------------
# Donation safety, snapshot/restore mid-stream, bit-exact replay.
# --------------------------------------------------------------------------

def _two_tenant_service():
    svc = SchedulerService()
    scfg, ch = _configs()
    svc.add_tenant("a", scfg, ch)
    svc.add_tenant("b", SchedulerConfig(n_clients=70, model_bits=1e6,
                                        lam=2.0, V=300.0),
                   ChannelConfig(n_clients=70, p_max=60.0),
                   policy="uniform", m_avg=6.0)
    return svc


def _random_flushes(svc, n_flushes, seed=11):
    key = jax.random.PRNGKey(seed)
    out = []
    for r in range(n_flushes):
        for i, (nm, n) in enumerate([("a", N), ("b", 70)]):
            k = jax.random.fold_in(jax.random.fold_in(key, r), i)
            gains = np.abs(np.asarray(jax.random.normal(k, (n,)))) + 0.01
            svc.submit(nm, gains, key=jax.random.fold_in(k, 1))
        out.append(svc.flush())
    return out


def _per_tenant(dicts):
    """Collect response dicts into per-tenant decision sequences (live
    flush responses and per-entry replay responses group differently —
    the served order per tenant is the comparable thing)."""
    out = {}
    for d in dicts:
        for nm, dec in d.items():
            out.setdefault(nm, []).append(dec)
    return out


def _assert_tenant_sequences_equal(live, replayed):
    a, b = _per_tenant(live), _per_tenant(replayed)
    assert set(a) == set(b)
    for nm in a:
        assert len(a[nm]) == len(b[nm]), nm
        for r, (x, y) in enumerate(zip(a[nm], b[nm])):
            np.testing.assert_array_equal(x.sel, y.sel,
                                          err_msg=f"{nm} serve {r}")
            np.testing.assert_array_equal(x.q, y.q,
                                          err_msg=f"{nm} serve {r}")
            np.testing.assert_array_equal(x.p, y.p,
                                          err_msg=f"{nm} serve {r}")
            np.testing.assert_array_equal(x.t_comm, y.t_comm)
            np.testing.assert_array_equal(x.power, y.power)


def test_donation_snapshot_restore_replay_bitexact(tmp_path):
    """Stepping twice from a snapshot equals replay: donated buffers never
    corrupt semantics, and a restored service reproduces the logged
    session bit for bit — including through the npz file round trips."""
    svc = _two_tenant_service()
    _random_flushes(svc, 2, seed=5)          # pre-roll: non-trivial queues
    svc.save(str(tmp_path / "state.npz"))    # snapshot mid-stream
    mark = len(svc.log)
    live = _random_flushes(svc, 3, seed=6)   # serve on (donating state)
    svc.log.save(str(tmp_path / "log.npz"))

    from repro.service import RequestLog
    structures = {n: svc.raw_structure(n) for n in ("a", "b")}
    log = RequestLog.load(str(tmp_path / "log.npz"), structures)
    assert len(log) == len(svc.log) and log.n_requests == svc.log.n_requests

    svc2 = _two_tenant_service()
    svc2.load(str(tmp_path / "state.npz"))   # restore the snapshot
    replay_log = RequestLog()
    replay_log.entries = log.entries[mark:]  # the post-snapshot session
    replayed = replay_log.replay(svc2)
    _assert_tenant_sequences_equal(live, replayed)
    # final queue state identical too
    for nm in ("a", "b"):
        s1, s2 = svc.tenant_state(nm), svc2.tenant_state(nm)
        np.testing.assert_array_equal(s1.z, s2.z, err_msg=nm)
        np.testing.assert_array_equal(s1.aux, s2.aux, err_msg=nm)
        assert int(s1.t) == int(s2.t)


def test_same_tenant_twice_in_one_flush_serves_in_order():
    """k submissions in one flush = k waves in submission order — state
    advances identically to k single-request flushes."""
    scfg, ch = _configs()
    sig = heterogeneous_sigmas(N)
    stream = _engine_stream(jax.random.PRNGKey(4), scfg, ch, sig, 4)

    svc_one = SchedulerService()
    svc_one.add_tenant("t", scfg, ch)
    for r in stream:
        svc_one.submit("t", r["gains"], raw=r["raw"])
    last = svc_one.flush()["t"]              # 4 waves inside one flush

    svc_seq = SchedulerService()
    svc_seq.add_tenant("t", scfg, ch)
    seq = _drive_service(svc_seq, "t", stream)
    np.testing.assert_array_equal(last.q, seq[-1].q)
    np.testing.assert_array_equal(last.sel, seq[-1].sel)
    for nm, s1, s2 in [("t", svc_one.tenant_state("t"),
                        svc_seq.tenant_state("t"))]:
        np.testing.assert_array_equal(s1.z, s2.z, err_msg=nm)
        assert int(s1.t) == int(s2.t) == 4


# --------------------------------------------------------------------------
# Validation and failure atomicity.
# --------------------------------------------------------------------------

def test_validation_errors():
    svc = SchedulerService()
    scfg, ch = _configs()
    svc.add_tenant("t", scfg, ch)
    with pytest.raises(ValueError, match="already registered"):
        svc.add_tenant("t", scfg, ch)
    with pytest.raises(ValueError, match="not servable"):
        svc.add_tenant("ua", scfg, ch, policy="update_aware", m_avg=3.0)
    with pytest.raises(ValueError, match="m_avg > 0"):
        svc.add_tenant("u", scfg, ch, policy="uniform")
    with pytest.raises(KeyError):
        svc.submit("ghost", np.ones(N, np.float32),
                   key=jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="shape"):
        svc.submit("t", np.ones(N + 1, np.float32),
                   key=jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="exactly one"):
        svc.submit("t", np.ones(N, np.float32))
    with pytest.raises(ValueError, match="unknown solver"):
        SchedulerService(solver="magma")
    # non-positive gains would tie greedy's sort threshold with the 0.0
    # pad fill (pad lanes selected) — rejected up front
    bad = np.ones(N, np.float32)
    bad[3] = 0.0
    with pytest.raises(ValueError, match="positive"):
        svc.submit("t", bad, key=jax.random.PRNGKey(0))
    # greedy with m > N cannot even build in the engine (sort[m-1] is out
    # of range); with bucket padding it would select pad lanes instead
    with pytest.raises(ValueError, match="m_avg"):
        svc.add_tenant("g", *_configs(), policy="greedy_channel",
                       m_avg=N + 1.0)


def test_failed_flush_logs_nothing():
    """A flush whose FIRST serve group raises must not be recorded in the
    replay log (the log must contain exactly the requests whose queue
    updates happened, or replay diverges)."""
    scfg, ch = _configs(n=64)
    svc = SchedulerService()
    svc.add_tenant("x", scfg, ch)
    svc.add_tenant("y", dataclasses.replace(scfg, V=17.0), ch)
    gains = np.ones(64, np.float32)
    svc.submit("x", gains, key=jax.random.PRNGKey(0))
    svc.submit("y", gains, key=jax.random.PRNGKey(1))

    def boom(*args, **kw):
        raise RuntimeError("injected first-group failure")

    svc._dispatch_group = boom
    with pytest.raises(RuntimeError, match="injected"):
        svc.flush()
    assert len(svc.log) == 0 and svc.log.n_requests == 0


def test_flush_failure_midway_replay_stays_bitexact():
    """The headline failure-atomicity fix: a flush that raises on wave 2
    of 3 has already advanced queue state for wave 1 — the log must hold
    EXACTLY that wave, so replay from the last snapshot reproduces the
    live (partially-advanced) state bit for bit."""
    from repro.service import RequestLog

    scfg, ch = _configs()
    svc = SchedulerService()
    svc.add_tenant("t", scfg, ch)
    key = jax.random.PRNGKey(21)
    gains = [np.abs(np.asarray(jax.random.normal(
        jax.random.fold_in(key, r), (N,)))) + 0.01 for r in range(4)]
    svc.submit("t", gains[0], key=jax.random.fold_in(key, 100))
    svc.flush()                              # pre-roll: non-trivial queues
    snap = svc.snapshot()
    mark = len(svc.log)

    for r in range(3):                       # same tenant 3x -> 3 waves
        svc.submit("t", gains[1 + r], key=jax.random.fold_in(key, 200 + r))
    orig = svc._dispatch_group
    calls = {"n": 0}

    def boom(*args, **kw):
        calls["n"] += 1
        if calls["n"] == 2:
            raise RuntimeError("injected wave-2 failure")
        return orig(*args, **kw)

    svc._dispatch_group = boom
    with pytest.raises(RuntimeError, match="injected"):
        svc.flush()
    svc._dispatch_group = orig
    assert calls["n"] == 2
    # exactly the served wave was logged; the failed + unserved ones not
    assert len(svc.log) == mark + 1
    assert int(svc.tenant_state("t").t) == 2   # pre-roll + wave 1 only

    svc2 = SchedulerService()
    svc2.add_tenant("t", scfg, ch)
    svc2.restore(snap)
    tail = RequestLog()
    tail.entries = svc.log.entries[mark:]
    tail.replay(svc2, restore=False)
    s1, s2 = svc.tenant_state("t"), svc2.tenant_state("t")
    np.testing.assert_array_equal(s1.z, s2.z)
    np.testing.assert_array_equal(s1.aux, s2.aux)
    assert int(s1.t) == int(s2.t)


def test_submit_rejects_nonfinite_gains():
    """`np.all(gains > 0)` alone admits +inf, which poisons the Theorem-2
    solve (log2 of inf SNR) and NaN-contaminates the shared bucket batch
    — non-finite gains must be rejected at submit, leaving nothing
    queued."""
    scfg, ch = _configs()
    svc = SchedulerService()
    svc.add_tenant("t", scfg, ch)
    for poison in (np.inf, -np.inf, np.nan):
        bad = np.ones(N, np.float32)
        bad[7] = poison
        with pytest.raises(ValueError, match="finite"):
            svc.submit("t", bad, key=jax.random.PRNGKey(0))
    assert svc.n_queued == 0 and len(svc.log) == 0


# --------------------------------------------------------------------------
# Tenant lifecycle: admission, eviction/spill/reload, log compaction.
# --------------------------------------------------------------------------

def test_add_tenant_preserves_sibling_queues_bitwise():
    """Admitting a new tenant into a non-empty bucket must not reset the
    sibling tenants' live Z-queues: serve A for 5 rounds, admit B into
    A's bucket, and A's next decision is bitwise-unchanged vs a no-add
    control."""
    scfg, ch = _configs()
    sig = heterogeneous_sigmas(N)
    stream = _engine_stream(jax.random.PRNGKey(7), scfg, ch, sig, 6)

    ctrl = SchedulerService()
    ctrl.add_tenant("a", scfg, ch)
    test = SchedulerService()
    test.add_tenant("a", scfg, ch)
    for r in stream[:5]:
        ctrl.submit("a", r["gains"], raw=r["raw"])
        ctrl.flush()
        test.submit("a", r["gains"], raw=r["raw"])
        test.flush()
    # same N -> same bucket key; different V exercises the coeff restack
    test.add_tenant("b", dataclasses.replace(scfg, V=321.0), ch)
    sa, sc = test.tenant_state("a"), ctrl.tenant_state("a")
    np.testing.assert_array_equal(sa.z, sc.z)      # admission reset check
    r = stream[5]
    ctrl.submit("a", r["gains"], raw=r["raw"])
    test.submit("a", r["gains"], raw=r["raw"])
    da, dc = test.flush()["a"], ctrl.flush()["a"]
    _assert_decisions_equal(da, {**r, "sel": dc.sel, "q": dc.q, "p": dc.p,
                                 "t_comm": dc.t_comm, "power": dc.power,
                                 "n_sel": int(dc.n_sel)},
                            msg="after admitting sibling")


def test_evict_spill_reload_bitwise_vs_never_evicted(tmp_path):
    """evict -> spill (through the checkpoint substrate on disk) ->
    reload -> serve is bitwise-equal to never having evicted — including
    for the SIBLING tenant whose row shifts when the bucket compacts."""
    scfg, ch = _configs()
    sib = dataclasses.replace(scfg, V=44.0, lam=3.0)  # same bucket as "a"
    uni_s = SchedulerConfig(n_clients=70, model_bits=1e6, lam=2.0, V=300.0)
    uni_c = ChannelConfig(n_clients=70, p_max=60.0)

    def build(spill_dir=None):
        svc = SchedulerService(spill_dir=spill_dir)
        svc.add_tenant("a", scfg, ch)
        svc.add_tenant("c", sib, ch)
        svc.add_tenant("b", uni_s, uni_c, policy="uniform", m_avg=6.0)
        return svc

    base, lc = build(), build(spill_dir=str(tmp_path))
    key = jax.random.PRNGKey(31)

    def serve(names, r):
        out = {}
        for svc in (base, lc):
            for i, nm in enumerate(names):
                n = {"a": N, "c": N, "b": 70}[nm]
                k = jax.random.fold_in(jax.random.fold_in(key, r), i)
                g = np.abs(np.asarray(jax.random.normal(k, (n,)))) + 0.01
                svc.submit(nm, g, key=jax.random.fold_in(k, 1))
            out[svc] = svc.flush()
        return out[base], out[lc]

    for r in range(3):
        serve(("a", "c", "b"), r)
    lc.evict("a")                       # bucket compacts; "c" row shifts
    assert lc.spilled == ("a",)
    import glob
    assert glob.glob(str(tmp_path / "spill-*.npz"))   # really on disk
    for r in range(3, 5):               # "a" idle on base, evicted on lc
        db, dl = serve(("c", "b"), r)
        for nm in ("c", "b"):           # sibling unharmed by compaction
            np.testing.assert_array_equal(db[nm].q, dl[nm].q, err_msg=nm)
            np.testing.assert_array_equal(db[nm].sel, dl[nm].sel)
    lc.reload("a")
    assert lc.spilled == ()
    for r in range(5, 7):
        db, dl = serve(("a", "c", "b"), r)
        for nm in ("a", "c", "b"):
            np.testing.assert_array_equal(db[nm].sel, dl[nm].sel,
                                          err_msg=f"{nm} round {r}")
            np.testing.assert_array_equal(db[nm].q, dl[nm].q)
            np.testing.assert_array_equal(db[nm].p, dl[nm].p)
            np.testing.assert_array_equal(db[nm].t_comm, dl[nm].t_comm)
    for nm in ("a", "c", "b"):
        s1, s2 = base.tenant_state(nm), lc.tenant_state(nm)
        np.testing.assert_array_equal(s1.z, s2.z, err_msg=nm)
        np.testing.assert_array_equal(s1.aux, s2.aux, err_msg=nm)
        assert int(s1.t) == int(s2.t)


def test_evict_lru_and_auto_reload_on_submit():
    """evict_lru picks the least-recently-served tenant; a submit to an
    evicted tenant transparently reloads it."""
    svc = _two_tenant_service()
    _random_flushes(svc, 1, seed=3)
    # "a" was submitted before "b" each flush, but both were touched;
    # touch "a" again so "b" is the LRU
    svc.submit("a", np.ones(N, np.float32), key=jax.random.PRNGKey(5))
    svc.flush()
    assert svc.evict_lru() == "b"
    assert "b" not in svc.store and svc.spilled == ("b",)
    with pytest.raises(ValueError, match="reload"):
        svc.add_tenant("b", SchedulerConfig(n_clients=70, model_bits=1e6),
                       ChannelConfig(n_clients=70))
    svc.submit("b", np.ones(70, np.float32), key=jax.random.PRNGKey(6))
    assert "b" in svc.store           # auto-reloaded
    d = svc.flush()["b"]
    assert d.sel.shape == (70,)
    # queued requests pin a tenant: not evictable
    svc.submit("a", np.ones(N, np.float32), key=jax.random.PRNGKey(7))
    with pytest.raises(ValueError, match="queued"):
        svc.evict("a")
    svc.flush()


def test_compacted_log_replay_equals_full_log_replay(tmp_path):
    """compact_log() drops served entries and records the snapshot in
    the log; replaying the compacted log equals replaying the full log —
    and the live service — bit for bit, including through npz
    save/load."""
    from repro.service import RequestLog

    svc = _two_tenant_service()
    start = svc.snapshot()
    _random_flushes(svc, 2, seed=5)
    full_entries = [list(e) for e in svc.log.entries]
    svc.compact_log()
    assert len(svc.log) == 0 and svc.log.n_compacted == len(full_entries)
    live = _random_flushes(svc, 3, seed=6)
    full_entries += [list(e) for e in svc.log.entries]

    # compacted-log replay (snapshot rides the log npz)
    svc.log.save(str(tmp_path / "log.npz"))
    structures = {n: svc.raw_structure(n) for n in ("a", "b")}
    loaded = RequestLog.load(str(tmp_path / "log.npz"), structures)
    assert loaded.snapshot is not None
    assert loaded.n_compacted == svc.log.n_compacted
    svc2 = _two_tenant_service()
    replayed = loaded.replay(svc2)          # restores the snapshot itself
    _assert_tenant_sequences_equal(live, replayed)

    # full-log replay from the start state reaches the same final bits
    full = RequestLog()
    full.entries = full_entries
    svc3 = _two_tenant_service()
    svc3.restore(start)
    full.replay(svc3, restore=False)
    for nm in ("a", "b"):
        s1, s2, s3 = (svc.tenant_state(nm), svc2.tenant_state(nm),
                      svc3.tenant_state(nm))
        np.testing.assert_array_equal(s1.z, s2.z, err_msg=nm)
        np.testing.assert_array_equal(s2.z, s3.z, err_msg=nm)
        assert int(s1.t) == int(s2.t) == int(s3.t)
    # compacting with queued requests would lose them from the log
    svc.submit("a", np.ones(N, np.float32), key=jax.random.PRNGKey(8))
    with pytest.raises(ValueError, match="flush"):
        svc.compact_log()
    svc.flush()


# --------------------------------------------------------------------------
# Staged arenas: bitwise parity with the pad-per-request path + warmup.
# --------------------------------------------------------------------------

def test_staged_path_bitwise_equals_pad_per_flush_path():
    """The staged-arena batch build is bitwise-equal to the PR-5
    pad-per-request build on a mixed-bucket workload with multi-wave
    flushes (same compiled programs, same inputs, same bits)."""
    scfg, ch = _configs()
    uni_s = SchedulerConfig(n_clients=70, model_bits=1e6, lam=2.0, V=300.0)
    uni_c = ChannelConfig(n_clients=70, p_max=60.0)
    gre_s = SchedulerConfig(n_clients=21, model_bits=2e6, V=50.0)
    gre_c = ChannelConfig(n_clients=21, p_max=80.0)

    def build(staging):
        svc = SchedulerService(staging=staging)
        svc.add_tenant("a", scfg, ch)
        svc.add_tenant("c", dataclasses.replace(scfg, V=44.0), ch)
        svc.add_tenant("u", uni_s, uni_c, policy="uniform", m_avg=6.0)
        svc.add_tenant("g", gre_s, gre_c, policy="greedy_channel",
                       m_avg=4.0)
        return svc

    staged, legacy = build(True), build(False)
    assert staged.staging and not legacy.staging
    key = jax.random.PRNGKey(13)
    live_s, live_l = [], []
    for r in range(4):
        for i, (nm, n) in enumerate(
                [("a", N), ("c", N), ("u", 70), ("g", 21), ("a", N)]):
            k = jax.random.fold_in(jax.random.fold_in(key, r), i)
            g = np.abs(np.asarray(jax.random.normal(k, (n,)))) + 0.01
            kk = jax.random.fold_in(k, 1)
            staged.submit(nm, g, key=kk)    # "a" twice -> 2 waves
            legacy.submit(nm, g, key=kk)
        live_s.append(staged.flush())
        live_l.append(legacy.flush())
    _assert_tenant_sequences_equal(live_l, live_s)
    for nm in ("a", "c", "u", "g"):
        s1, s2 = staged.tenant_state(nm), legacy.tenant_state(nm)
        np.testing.assert_array_equal(s1.z, s2.z, err_msg=nm)
        assert int(s1.t) == int(s2.t)


def test_warmup_leaves_state_bitwise_untouched():
    """warmup() serves all-sentinel batches — every row is scatter-
    dropped, so tenant state is bitwise-identical before and after, and
    the next real decision matches a no-warmup control."""
    svc = _two_tenant_service()
    _random_flushes(svc, 1, seed=9)
    before = svc.snapshot()
    svc.warmup(max_batch=8)
    after = svc.snapshot()
    for k in before:
        np.testing.assert_array_equal(before[k].z, after[k].z, err_msg=k)
        np.testing.assert_array_equal(before[k].aux, after[k].aux)
        np.testing.assert_array_equal(before[k].t, after[k].t)
    ctrl = _two_tenant_service()
    _random_flushes(ctrl, 1, seed=9)
    d1 = _random_flushes(svc, 1, seed=10)[0]
    d2 = _random_flushes(ctrl, 1, seed=10)[0]
    for nm in ("a", "b"):
        np.testing.assert_array_equal(d1[nm].q, d2[nm].q, err_msg=nm)
        np.testing.assert_array_equal(d1[nm].sel, d2[nm].sel, err_msg=nm)


# --------------------------------------------------------------------------
# One device-to-host transfer a serve group: the packed step output.
# --------------------------------------------------------------------------

def _as_six(*outs):
    return outs


@pytest.mark.parametrize("n", [40, 100, 3597], ids=["b64", "b128", "b4096"])
@pytest.mark.parametrize("solver,staging", [("jnp", True), ("jnp", False),
                                            ("pallas_fused", True)],
                         ids=["jnp", "jnp-legacy", "pallas_fused"])
def test_packed_pull_serves_the_steps_outputs_bitwise(monkeypatch, solver,
                                                      staging, n):
    """Each served Decision is, bit for bit and dtype for dtype, the
    step's six outputs as the step computed them before packing, and each
    serve group costs one counted device-to-host transfer — whichever
    batch builder (staged arenas or the legacy per-request pad) fed it."""
    from repro.service import batching
    from repro.service import step as step_mod

    groups = []
    real = batching.make_bucket_step

    def make(*a, **k):
        step, ref = real(*a, **k), real(*a, **k)

        def recorded(state, *args):
            # the same step with packing left out, on a copy of the
            # (donated) state: the six outputs the packed array carries
            with monkeypatch.context() as m:
                m.setattr(step_mod, "pack_outputs", _as_six)
                outs, _ = ref(jax.tree.map(jnp.copy, state), *args)
            groups.append([np.asarray(x) for x in outs])
            return step(state, *args)
        return recorded
    monkeypatch.setattr(batching, "make_bucket_step", make)

    scfg, ch = _configs(n=n)
    svc = SchedulerService(solver=solver, staging=staging, telemetry=True)
    for name in ("a", "b"):
        svc.add_tenant(name, scfg, ch)
    rng = np.random.default_rng(n)
    for t, name in enumerate(("a", "b", "a")):   # waves [a, b] and [a]
        svc.submit(name, rng.uniform(0.05, 3.0, n).astype(np.float32),
                   key=jax.random.PRNGKey(t))
    out = svc.flush()
    assert len(groups) == 2
    for name, (g, i) in {"a": (1, 0), "b": (0, 1)}.items():
        sel, q, p, t_comm, power, n_sel = groups[g]
        want = (sel[i, :n], q[i, :n], p[i, :n], t_comm[i], power[i],
                np.int64(n_sel[i]))
        for field, got, w in zip(out[name]._fields, out[name], want):
            assert got.dtype == w.dtype, (name, field)
            assert got.tobytes() == w.tobytes(), (name, field)
    assert [x.dtype for x in out["a"]] == [
        np.bool_, np.float32, np.float32, np.float32, np.float32, np.int64]
    reg = svc.obs.registry
    assert reg.total("service_flush_transfers_total") == 2
    assert reg.total("service_groups_served_total") == 2


def _f32(*bit_patterns):
    return np.array(bit_patterns, np.uint32).view(np.float32)


@pytest.mark.parametrize("n_bucket", [8, 128, 4096])
def test_pack_unpack_round_trip_keeps_every_bit(n_bucket):
    """-0.0, denormals, infinities and NaN payloads (quiet and signalling,
    either sign) survive the device-side pack and the host-side unpack
    bit for bit, in every column of the layout."""
    from repro.service.step import (pack_outputs, packed_layout,
                                    unpack_outputs)

    special = _f32(0x80000000, 0x00000001, 0x807fffff, 0x7f800000,
                   0xff800000, 0x7fc00000, 0x7fc12345, 0x7f800001,
                   0xffbfffff, 0xffffffff)
    rng = np.random.default_rng(n_bucket)
    b = 3
    lanes = rng.integers(0, 2**32, (2, b, n_bucket),
                         dtype=np.uint32).view(np.float32)
    lanes[:, :, :special.size] = special[:n_bucket]
    q, p = lanes
    t_comm, power = special[:b], special[-b:]
    sel = rng.random((b, n_bucket)) < 0.5
    n_sel = np.array([0, n_bucket, 2**31 - 1], np.int32)
    want = (sel, q, p, t_comm, power, n_sel)

    packed = jax.jit(pack_outputs)(*want)
    assert packed.dtype == jnp.uint32
    assert packed.shape == (b, packed_layout(n_bucket).width)
    got = unpack_outputs(np.asarray(packed), n_bucket)
    for i, (g, w) in enumerate(zip(got, want)):
        assert g.dtype == w.dtype and g.shape == w.shape, i
        assert g.tobytes() == w.tobytes(), i
