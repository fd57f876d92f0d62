"""Benchmark harness — one entry per paper figure + roofline + kernels.

``python -m benchmarks.run``            — default profile (single-core CPU
                                          budget: reduced rounds, see
                                          benchmarks/figures.py)
``python -m benchmarks.run --smoke``    — minutes-scale CI check
``python -m benchmarks.run --full``     — paper-scale (hours on this host)
``python -m benchmarks.run --only fig5_power,kernels``

Output: ``name,us_per_call,derived`` CSV lines per the repo convention,
plus per-figure JSON dumps under benchmarks/out/.
"""

from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

from repro.launch.compile_cache import enable_compile_cache
from repro.launch.distributed import is_main, main_print

OUT_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "out")


def _emit(name: str, us_per_call: float, derived: str):
    # rank-0 gated: a multi-process run emits ONE csv stream, not one per
    # process (repro/launch/distributed.py).
    main_print(f"{name},{us_per_call:.1f},{derived}")


def _dump(name: str, obj):
    if not is_main():
        return
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, f"{name}.json"), "w") as f:
        json.dump(obj, f, default=lambda o: np.asarray(o).tolist())


# ----------------------------------------------------------- figure benches

def bench_fig2_cifar(prof):
    """Fig. 2: CIFAR-10 time-to-accuracy, proposed vs M-matched uniform."""
    from benchmarks.figures import run_policy
    from repro.fl.simulation import time_to_accuracy

    results = {}
    for lam in (10.0, 100.0):
        for policy in ("proposed", "uniform"):
            t0 = time.time()
            h = run_policy("cifar10", "heterogeneous", lam, policy, prof)
            wall = time.time() - t0
            key = f"lam{int(lam)}_{policy}"
            results[key] = h
            target = 0.9 * float(max(h["test_acc"]))
            tta = time_to_accuracy(h, target)
            _emit(f"fig2_cifar_{key}", wall * 1e6 / prof.rounds,
                  f"acc={h['test_acc'][-1]:.3f};comm_s={h['comm_time'][-1]:.1f};"
                  f"tta90={tta if tta else 'NA'}")
    for lam in (10, 100):
        p = results[f"lam{lam}_proposed"]["comm_time"][-1]
        u = results[f"lam{lam}_uniform"]["comm_time"][-1]
        _emit(f"fig2_cifar_comm_saving_lam{lam}", 0.0,
              f"proposed/uniform_comm_time={p / u:.3f}")
    _dump("fig2_cifar", results)
    return results


def bench_fig3_lambda(prof, fig2=None):
    """Fig. 3: per-round convergence slows as lambda grows (fewer devices)."""
    from benchmarks.figures import run_policy

    fig2 = fig2 or {}
    results = {}
    for lam in (10.0, 100.0):
        key = f"lam{int(lam)}_proposed"
        h = fig2.get(key)
        if h is None:
            h = run_policy("cifar10", "heterogeneous", lam, "proposed", prof)
        results[f"lam{int(lam)}"] = h
        # accuracy at the same ROUND index (not time)
        _emit(f"fig3_lambda{int(lam)}", 0.0,
              f"acc_final={h['test_acc'][-1]:.3f};"
              f"mean_selected={np.mean(h['n_selected']):.2f}")
    _dump("fig3_lambda", results)
    return results


def bench_fig4_femnist(prof):
    """Fig. 4: FEMNIST (non-iid writers), heterogeneous channels."""
    from benchmarks.figures import run_policy
    from repro.fl.simulation import time_to_accuracy

    results = {}
    for lam in (10.0, 100.0):
        for policy in ("proposed", "uniform"):
            t0 = time.time()
            h = run_policy("femnist", "heterogeneous", lam, policy, prof)
            wall = time.time() - t0
            key = f"lam{int(lam)}_{policy}"
            results[key] = h
            _emit(f"fig4_femnist_{key}", wall * 1e6 / prof.rounds,
                  f"acc={h['test_acc'][-1]:.3f};"
                  f"comm_s={h['comm_time'][-1]:.1f}")
    for lam in (10, 100):
        p = results[f"lam{lam}_proposed"]["comm_time"][-1]
        u = results[f"lam{lam}_uniform"]["comm_time"][-1]
        _emit(f"fig4_femnist_comm_saving_lam{lam}", 0.0,
              f"proposed/uniform_comm_time={p / u:.3f}")
    _dump("fig4_femnist", results)
    return results


def bench_fig5_power(prof):
    """Fig. 5: larger V -> slower convergence to the power constraint."""
    from benchmarks.figures import power_trajectory

    rounds = max(200, prof.rounds * 4)
    results = {}
    for v in (1.0, 1e3, 1e5):
        t0 = time.time()
        traj = power_trajectory(v, rounds=rounds)
        wall = time.time() - t0
        results[f"V{v:g}"] = traj
        # rounds until time-average power <= 1.05 * Pbar (Pbar = 1)
        ok = np.nonzero(traj <= 1.05)[0]
        tconv = int(ok[0]) if ok.size else -1
        _emit(f"fig5_power_V{v:g}", wall * 1e6 / rounds,
              f"rounds_to_constraint={tconv};final_avg_power={traj[-1]:.3f}")
    _dump("fig5_power", results)
    return results


# ---------------------------------------------------------------- roofline

def bench_roofline(prof):
    """Summaries from the production dry-run records, if present."""
    from benchmarks.roofline import load_records, roofline_terms

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(root, "dryrun_production.jsonl")
    if not os.path.exists(path):
        _emit("roofline", 0.0, "dryrun_production.jsonl missing (run "
              "python -m repro.launch.dryrun)")
        return
    recs = load_records(path)
    ok = [r for r in recs if r.get("status") == "OK"]
    doms = {}
    for r in ok:
        t = roofline_terms(r)
        doms[t["dominant"]] = doms.get(t["dominant"], 0) + 1
        _emit(f"roofline_{r['arch']}_{r['shape']}_{r['mesh']}", 0.0,
              f"compute={t['compute_s']:.3e};memory={t['memory_s']:.3e};"
              f"collective={t['collective_s']:.3e};dom={t['dominant']}")
    _emit("roofline_summary", 0.0,
          f"ok={len(ok)};skip={sum(1 for r in recs if 'SKIP' in r['status'])};"
          f"dominants={doms}")


# ------------------------------------------------------------------- engine

def bench_engine(prof):
    """Loop-vs-scan engine throughput and jnp-vs-Pallas Theorem-2 solve.

    Three layers, all steady-state (compiled functions warmed before timing,
    so the numbers isolate the *driving* strategy, not jit compile):

    * full simulation (channel -> schedule -> train -> account) at
      N in {128, 3597}, eval_every=10: the legacy engine's per-round
      jit-dispatch + host-sync pattern vs the scan engine's compiled
      chunks. Bounded below by the conv compute both engines share.
    * scheduling layer at N in {3597, 100k} (the 100k full sim would
      materialize a 100k-client dataset): per-round dispatch of the jitted
      schedule step vs the fully scan-compiled ``run_sweep`` round, where
      XLA fuses the elementwise channel -> solve -> select -> account chain
      and the per-call dispatch/sync disappears. This is where the big
      factor lives.
    * the jnp Theorem-2 solve at N in {128, 3597, 100k}.
    """
    import jax
    import jax.numpy as jnp
    from repro.core import (ChannelConfig, SchedulerConfig, channel_rate,
                            draw_gains, heterogeneous_sigmas, init_state,
                            schedule_step, solve_round)
    from repro.data.synthetic import make_cifar10_like
    from repro.fl.engine import (SimConfig, eval_rounds, init_carry,
                                 make_chunk_runner, make_sim_round,
                                 make_sweep_runner)
    from repro.fl.simulation import time_to_accuracy
    from repro.models.registry import make_model

    results = {}
    # steady-state timing window scales with the profile (smoke stays small)
    rounds = max(20, min(200, 2 * prof.rounds))

    # --- full simulation, loop vs scan -----------------------------------
    for n in (128, 3597):
        ds = make_cifar10_like(jax.random.PRNGKey(0), n_clients=n,
                               per_client=16, n_test=256, h=8, w=8)
        model_params = (("conv1", 4), ("conv2", 8), ("hidden", 16))
        spec = make_model("cnn", ds, **dict(model_params))
        params = spec.init_fn(jax.random.PRNGKey(1))
        ch = ChannelConfig(n_clients=n)
        scfg = SchedulerConfig(n_clients=n, model_bits=32 * 5000.0)
        sig = heterogeneous_sigmas(n)
        sim = SimConfig(rounds=rounds, eval_every=10, m_cap=2, batch=4,
                        local_steps=1, eval_size=256, model="cnn",
                        model_params=model_params)

        # legacy driving pattern: host split + per-round jit call + float()
        # syncs + separate eval call (exactly run_simulation_loop's loop)
        sim_round = jax.jit(make_sim_round(ds, sim, scfg, ch, sig),
                            donate_argnums=(0,))
        eval_acc = jax.jit(lambda p: spec.eval_fn(
            p, ds.test_images[:256], ds.test_labels[:256]))

        def drive_loop():
            p, pst, cst = init_carry(jax.random.PRNGKey(2), params,
                                      scfg, sim=sim, sigmas=sig, ch=ch)[:3]
            key = jax.random.PRNGKey(2)
            t_cum = 0.0
            for r in range(rounds):
                key, k = jax.random.split(key)
                p, pst, cst, t, pw, ns = sim_round(p, pst, cst, k)
                t_cum += float(t)
                _ = float(pw)
                if r % sim.eval_every == 0 or r == rounds - 1:
                    _ = float(eval_acc(p))
            return t_cum

        run_chunk = make_chunk_runner(ds, sim, scfg, ch, sig)

        def drive_scan():
            # history capture is part of the timed drive — the cost of
            # recording eval points belongs to the driving strategy
            carry = init_carry(jax.random.PRNGKey(2), params, scfg,
                               sim=sim, sigmas=sig, ch=ch)
            hist = {"round": [], "comm_time": [], "test_acc": []}
            prev = -1
            for r in eval_rounds(rounds, sim.eval_every):
                carry, acc, ns = run_chunk(carry, n_rounds=r - prev)
                prev = r
                hist["round"].append(r)
                hist["comm_time"].append(float(carry[4]))
                hist["test_acc"].append(float(acc))
            return {k: np.asarray(v) for k, v in hist.items()}

        drive_loop()   # warm both compiled paths
        drive_scan()
        t0 = time.time()
        drive_loop()
        wall_loop = time.time() - t0
        t0 = time.time()
        hist = drive_scan()
        wall_scan = time.time() - t0
        rps_loop, rps_scan = rounds / wall_loop, rounds / wall_scan
        speedup = rps_scan / rps_loop
        tta = time_to_accuracy(hist, 0.9 * float(max(hist["test_acc"])))
        results[f"sim_n{n}"] = {"rounds_per_sec_loop": rps_loop,
                                "rounds_per_sec_scan": rps_scan,
                                "speedup": speedup, "tta90_comm_s": tta,
                                "acc_final": float(hist["test_acc"][-1])}
        _emit(f"engine_sim_n{n}_loop", 1e6 / rps_loop,
              f"rounds_per_sec={rps_loop:.1f}")
        _emit(f"engine_sim_n{n}_scan", 1e6 / rps_scan,
              f"rounds_per_sec={rps_scan:.1f};speedup_vs_loop={speedup:.2f};"
              f"tta90_comm_s={tta if tta else 'NA'};"
              f"acc={hist['test_acc'][-1]:.3f}")

    # --- scheduling layer: per-round dispatch vs compiled scan -----------
    for n in (3597, 100_000):
        ch = ChannelConfig(n_clients=n)
        scfg = SchedulerConfig(n_clients=n, model_bits=32 * 555178.0)
        sig = heterogeneous_sigmas(n)

        @jax.jit
        def sched_step(k, state):
            k1, k2 = jax.random.split(k)
            gains = draw_gains(k1, sig, ch)
            sel, q, p, state = schedule_step(k2, gains, state, scfg, ch)
            t = jnp.sum(jnp.where(sel, scfg.model_bits / jnp.maximum(
                channel_rate(gains, p, ch), 1e-9), 0.0))
            return state, t

        def sched_loop():
            state, key = init_state(scfg), jax.random.PRNGKey(0)
            t_cum = 0.0
            for _ in range(rounds):
                key, k = jax.random.split(key)
                state, t = sched_step(k, state)
                t_cum += float(t)
            return t_cum

        runner = make_sweep_runner(sig, scfg, ch, rounds=rounds,
                                   policy="proposed")
        keys = jax.random.PRNGKey(0)[None, :]

        def sched_scan():
            out = runner(keys)
            jax.block_until_ready(out)
            return out

        sched_loop()   # warm both compiled paths
        sched_scan()
        t0 = time.time()
        sched_loop()
        wall_loop = time.time() - t0
        t0 = time.time()
        sched_scan()
        wall_scan = time.time() - t0
        rps_loop, rps_scan = rounds / wall_loop, rounds / wall_scan
        results[f"sched_n{n}"] = {"rounds_per_sec_loop": rps_loop,
                                  "rounds_per_sec_scan": rps_scan,
                                  "speedup": rps_scan / rps_loop}
        _emit(f"engine_sched_n{n}_loop", 1e6 / rps_loop,
              f"rounds_per_sec={rps_loop:.1f}")
        _emit(f"engine_sched_n{n}_scan", 1e6 / rps_scan,
              f"rounds_per_sec={rps_scan:.1f};"
              f"speedup_vs_loop={rps_scan / rps_loop:.2f}")

    # --- Theorem-2 solve: the jnp closed form -----------------------------
    for n in (128, 3597, 100_000):
        ch = ChannelConfig(n_clients=n)
        scfg = SchedulerConfig(n_clients=n, model_bits=32 * 555178.0)
        gains = jnp.exp(jax.random.normal(jax.random.PRNGKey(0), (n,)))
        z = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (n,)))
        solve = jax.jit(lambda g, zz, scfg=scfg, ch=ch:
                        solve_round(g, zz, scfg, ch))
        jax.block_until_ready(solve(gains, z))
        iters = 20
        t0 = time.time()
        for _ in range(iters):
            jax.block_until_ready(solve(gains, z))
        us = (time.time() - t0) / iters * 1e6
        results[f"solve_n{n}_jnp"] = us
        _emit(f"engine_solve_n{n}_jnp", us,
              f"per_client_ns={us * 1000 / n:.1f};mode=compiled")
    _dump("engine", results)
    return results


# --------------------------------------------------------------------- grid

def bench_grid(prof):
    """Scenario-grid throughput: one shard_map-compiled call over all
    devices vs the same configs run sequentially through per-config jitted
    runners (both steady-state, compiled paths warmed).

    Dispatch-bound sizes (tiny model, few rounds) are where device sharding
    pays: expect near-linear scaling in device count once the per-device
    config count saturates. Run under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (scripts/test.sh
    idiom) to see multi-device numbers on CPU.
    """
    import jax
    from repro.core import ChannelConfig, SchedulerConfig
    from repro.core.channel import resolve_sigmas
    from repro.data.synthetic import make_cifar10_like
    from repro.fl.engine import SimConfig, make_config_runner
    from repro.fl.grid import (GridSpec, grid_cell_inputs, make_grid_runner,
                               sim_for_config)
    from repro.models.registry import make_model

    n = 64
    ds = make_cifar10_like(jax.random.PRNGKey(0), n_clients=n,
                           per_client=16, n_test=128, h=8, w=8)
    model_params = (("conv1", 4), ("conv2", 8), ("hidden", 16))
    params = make_model("cnn", ds,
                        **dict(model_params)).init_fn(jax.random.PRNGKey(1))
    ch = ChannelConfig(n_clients=n)
    scfg = SchedulerConfig(n_clients=n, model_bits=32 * 5000.0)
    rounds = max(5, min(20, prof.rounds // 4))
    sim = SimConfig(rounds=rounds, eval_every=5, m_cap=2, batch=4,
                    local_steps=1, eval_size=128, uniform_m=4.0,
                    model="cnn", model_params=model_params)
    spec = GridSpec(
        channels=("rayleigh", ("gauss_markov", (("rho", 0.9),))),
        sigma_dists=("heterogeneous",),
        policies=("proposed", "uniform", "update_aware"),
        seeds=tuple(range(4)),
    )
    key = jax.random.PRNGKey(7)
    n_dev = len(jax.devices())

    runner, _ = make_grid_runner(ds, sim, scfg, ch, spec)
    sigma_ids, keys = grid_cell_inputs(key, spec, n_dev)

    def drive_grid():
        out = runner(params, sigma_ids, keys)
        jax.block_until_ready(out)
        return out

    # sequential reference: per-(channel, policy) jitted config runner
    # (compiled once per cell, reused across seeds), one config at a time
    seq_runners = []
    for ci, pi in spec.cells():
        one, sdist = sim_for_config(sim, spec, ci, 0, pi)
        seq_runners.append(
            make_config_runner(ds, one, scfg, ch, resolve_sigmas(sdist, n)))
    seed_keys = [jax.random.fold_in(key, s) for s in spec.seeds]

    def drive_seq():
        outs = []
        for r in seq_runners:
            for k in seed_keys:
                outs.append(r(params, k))
        jax.block_until_ready(outs)
        return outs

    drive_grid()   # warm both compiled paths
    drive_seq()
    t0 = time.time()
    drive_grid()
    wall_grid = time.time() - t0
    t0 = time.time()
    drive_seq()
    wall_seq = time.time() - t0
    c = spec.size
    cps_grid, cps_seq = c / wall_grid, c / wall_seq
    speedup = cps_grid / cps_seq
    _emit("grid_sequential", 1e6 / cps_seq, f"configs_per_sec={cps_seq:.2f}")
    _emit("grid_shard_map", 1e6 / cps_grid,
          f"configs_per_sec={cps_grid:.2f};devices={n_dev};"
          f"speedup_vs_sequential={speedup:.2f};configs={c}")
    _dump("grid", {"configs": c, "devices": n_dev, "rounds": rounds,
                   "configs_per_sec_grid": cps_grid,
                   "configs_per_sec_sequential": cps_seq,
                   "speedup": speedup})
    return {"speedup": speedup, "devices": n_dev}


# --------------------------------------------------------------- tournament

def bench_tournament(prof):
    """Policy tournament over adversarial scenarios: churn x outage x
    straggler x policy x seed in ONE compiled ``run_grid`` call, scored as
    regret-vs-oracle and time-to-accuracy (repro/fl/tournament.py).

    Timing is steady-state for the compiled grid call (warmed), with the
    host-side scoring included — scoring is part of what a tournament run
    costs. JSON artifact: benchmarks/out/tournament.json (full metric
    arrays + leaderboard).
    """
    import jax
    from repro.core import ChannelConfig, SchedulerConfig
    from repro.data.synthetic import make_cifar10_like
    from repro.fl.engine import SimConfig
    from repro.fl.tournament import run_tournament
    from repro.models.registry import make_model

    n = 64
    ds = make_cifar10_like(jax.random.PRNGKey(0), n_clients=n,
                           per_client=16, n_test=128, h=8, w=8)
    model_params = (("conv1", 4), ("conv2", 8), ("hidden", 16))
    params = make_model("cnn", ds,
                        **dict(model_params)).init_fn(jax.random.PRNGKey(1))
    ch = ChannelConfig(n_clients=n)
    scfg = SchedulerConfig(n_clients=n, model_bits=32 * 5000.0)
    rounds = max(5, min(20, prof.rounds // 4))
    sim = SimConfig(rounds=rounds, eval_every=5, m_cap=2, batch=4,
                    local_steps=1, eval_size=128, uniform_m=4.0,
                    model="cnn", model_params=model_params)
    kw = dict(
        channels=("rayleigh",
                  ("outage_burst", (("outage_p", 0.2), ("burst_len", 4.0)))),
        populations=((),
                     (("p_leave", 0.1), ("p_join", 0.2)),
                     (("p_fail", 0.25),)),
        policies=("proposed", "uniform", "greedy_channel"),
        seeds=tuple(range(2)),
    )
    key = jax.random.PRNGKey(7)
    n_dev = len(jax.devices())

    def drive():
        return run_tournament(key, params, ds, sim, scfg, ch, **kw)

    drive()   # warm the compiled grid call
    t0 = time.time()
    t = drive()
    wall = time.time() - t0
    n_cfg = (len(kw["channels"]) * len(kw["populations"])
             * len(kw["policies"]) * len(kw["seeds"]))
    cps = n_cfg / wall
    best = t["leaderboard"][0]
    _emit("tournament", 1e6 / cps,
          f"configs_per_sec={cps:.2f};configs={n_cfg};devices={n_dev};"
          f"best={best['policy']};best_regret_acc="
          f"{best['mean_regret_acc']:.4f}")
    _dump("tournament", {k: t[k] for k in
                         ("round", "comm_time", "test_acc", "avg_power",
                          "n_selected", "channels", "populations",
                          "sigma_dists", "policies", "seeds", "final_acc",
                          "regret_acc", "time_to_acc", "regret_tta",
                          "acc_target_frac", "metric_axes", "leaderboard")})
    return {"configs_per_sec": cps, "leaderboard": t["leaderboard"]}


# -------------------------------------------------------------------- round

def bench_round(prof):
    """Participant-sharded vs sequential round throughput at
    m_cap in {8, 32, 128} (run under the scripts/test.sh 8-virtual-device
    idiom to see multi-device numbers on CPU).

    Both paths drive the SAME compiled chunk runner machinery (steady
    state, warmed) on the same registry model; the only difference is
    ``SimConfig.participant_shards`` — 0 is the sequential ``lax.map`` over
    all participants, D shards it across the device mesh with the
    q-weighted aggregate as a psum. On hosts where virtual devices share a
    couple of physical cores the speedup saturates at the core count, not
    the device count (same caveat as bench_grid); the m_cap=128 row is
    where sharding matters — sequential participant training is why the
    engines historically capped m_cap ~32.
    """
    import dataclasses

    import jax
    from repro.core import (ChannelConfig, SchedulerConfig,
                            heterogeneous_sigmas)
    from repro.data.synthetic import make_cifar10_like
    from repro.fl.engine import SimConfig, init_carry, make_chunk_runner
    from repro.models.registry import make_model

    n = 256
    ds = make_cifar10_like(jax.random.PRNGKey(0), n_clients=n,
                           per_client=16, n_test=128, h=8, w=8)
    model_params = (("conv1", 4), ("conv2", 8), ("hidden", 16))
    params = make_model("cnn", ds,
                        **dict(model_params)).init_fn(jax.random.PRNGKey(1))
    ch = ChannelConfig(n_clients=n)
    scfg = SchedulerConfig(n_clients=n, model_bits=32 * 5000.0)
    sig = heterogeneous_sigmas(n)
    n_dev = len(jax.devices())
    rounds = max(4, min(16, prof.rounds // 2))
    results = {"devices": n_dev, "rounds": rounds, "m_cap": {}}

    for m_cap in (8, 32, 128):
        base = SimConfig(rounds=rounds, eval_every=rounds, m_cap=m_cap,
                         batch=4, local_steps=1, eval_size=128, model="cnn",
                         model_params=model_params)
        walls = {}
        for label, sim in (("sequential", base),
                           ("sharded", dataclasses.replace(
                               base, participant_shards=n_dev))):
            run_chunk = make_chunk_runner(ds, sim, scfg, ch, sig)

            def drive():
                carry = init_carry(jax.random.PRNGKey(2), params, scfg,
                                   sim=sim, sigmas=sig, ch=ch)
                out = run_chunk(carry, n_rounds=rounds)
                jax.block_until_ready(out)

            drive()            # warm the compiled path
            t0 = time.time()
            drive()
            walls[label] = time.time() - t0
        rps = {k: rounds / w for k, w in walls.items()}
        speedup = rps["sharded"] / rps["sequential"]
        results["m_cap"][m_cap] = {
            "rounds_per_sec_sequential": rps["sequential"],
            "rounds_per_sec_sharded": rps["sharded"],
            "participants_per_sec_sharded": rps["sharded"] * m_cap,
            "speedup": speedup,
        }
        _emit(f"round_m{m_cap}_sequential", 1e6 / rps["sequential"],
              f"rounds_per_sec={rps['sequential']:.1f}")
        _emit(f"round_m{m_cap}_sharded", 1e6 / rps["sharded"],
              f"rounds_per_sec={rps['sharded']:.1f};devices={n_dev};"
              f"speedup_vs_sequential={speedup:.2f};"
              f"participants_per_sec={rps['sharded'] * m_cap:.0f}")
    _dump("round", results)
    return results


# ------------------------------------------------------------------ massive

def bench_massive(prof):
    """Client-sharded vs sequential scheduling-layer rounds/s at
    N in {10^4, 10^5, 10^6}, plus the solve-only cost per size.

    This is the hot path the client-sharded engine (fl/client_shard.py)
    exists for: the aggregator re-solves Theorem 2 for EVERY client EVERY
    round from instantaneous CSI, so at MEC scale the per-round pipeline is
    channel step -> solve -> Bernoulli select -> pack -> account over an
    (N,) vector. Both paths drive the same compiled
    ``make_schedule_runner`` scan (steady state, warmed); the only
    difference is ``client_shards`` — 0 keeps the (N,) pipeline on one
    device, D shards the client axis with scalars + packed indices as the
    only cross-device traffic.

    Run under the scripts/test.sh 8-virtual-device idiom for multi-device
    numbers on CPU. Honest caveat (same as bench_grid/bench_round): on this
    2-physical-core container the 8 virtual devices SHARE the cores AND
    XLA already multithreads the sequential reduce, so the sharded path's
    speedup here is bounded by core count, not device count — flat-to-
    losing numbers on this host are expected and recorded as measured;
    real meshes (one core/accelerator per shard) are where the N/D scaling
    pays. Compile wall-time is reported too: at N=10^6 the sequential
    XLA program's compile+run budget is itself a scaling obstacle.
    """
    import jax
    import jax.numpy as jnp
    from repro.core import (ChannelConfig, SchedulerConfig,
                            heterogeneous_sigmas, solve_round)
    from repro.fl.client_shard import make_schedule_runner

    n_dev = len(jax.devices())
    rounds = max(4, min(12, prof.rounds // 2))
    results = {"devices": n_dev, "rounds": rounds, "n": {}}
    for n in (10_000, 100_000, 1_000_000):
        ch = ChannelConfig(n_clients=n)
        scfg = SchedulerConfig(n_clients=n, model_bits=32 * 555178.0)
        sig = heterogeneous_sigmas(n)
        key = jax.random.PRNGKey(0)
        entry = {}
        for label, d in (("sequential", 0), ("sharded", n_dev)):
            runner = make_schedule_runner(sig, scfg, ch, rounds=rounds,
                                          policy="proposed",
                                          client_shards=d)
            t0 = time.time()
            out = runner(key)
            jax.block_until_ready(out)
            compile_wall = time.time() - t0
            t0 = time.time()
            out = runner(key)
            jax.block_until_ready(out)
            wall = time.time() - t0
            rps = rounds / wall
            entry[label] = {"rounds_per_sec": rps,
                            "compile_plus_first_run_s": compile_wall}
            _emit(f"massive_n{n}_{label}", 1e6 / rps,
                  f"rounds_per_sec={rps:.2f};devices={n_dev if d else 1};"
                  f"compile_s={compile_wall:.1f}")
        entry["speedup"] = (entry["sharded"]["rounds_per_sec"]
                            / entry["sequential"]["rounds_per_sec"])
        # solve-only: the Theorem-2 closed form alone at this N
        gains = jnp.exp(jax.random.normal(jax.random.PRNGKey(1), (n,)))
        z = jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (n,))) * 10
        solve = jax.jit(lambda g, zz, scfg=scfg, ch=ch:
                        solve_round(g, zz, scfg, ch))
        jax.block_until_ready(solve(gains, z))
        iters = 10
        t0 = time.time()
        for _ in range(iters):
            jax.block_until_ready(solve(gains, z))
        solve_us = (time.time() - t0) / iters * 1e6
        entry["solve_jnp_us"] = solve_us
        _emit(f"massive_n{n}_solve", solve_us,
              f"per_client_ns={solve_us * 1000 / n:.1f};"
              f"speedup_sharded={entry['speedup']:.2f}")
        # decision-only: the full per-round decision step (solve + select +
        # Eq. 9 + accounting), stitched vs the fused megakernel drop-in —
        # the solver="pallas_fused" hot path at this N. Off-TPU the fused
        # row runs the kernel in interpret mode (validation penalty, not
        # kernel speed); see bench_kernels for the labelled pair.
        from repro.core import make_policy
        from repro.core.policies import init_policy_state
        from repro.fl.decision import (decision_coeffs, decision_step,
                                       make_fused_decision)
        co = decision_coeffs(scfg, ch)
        st = init_policy_state("proposed", n)._replace(
            z=jnp.abs(jax.random.normal(jax.random.PRNGKey(2),
                                        (n,))).astype(jnp.float32) * 10)
        gains32 = gains.astype(jnp.float32)

        def stitched(co, k, g, s):
            step = make_policy("proposed", scfg, ch, coeffs=co.solve)
            return decision_step(step, co.acct, k, g, s)

        def fused(co, k, g, s):
            return make_fused_decision(scfg, co)(None, None, k, g, s)

        for label, fn in (("stitched", stitched), ("fused", fused)):
            f = jax.jit(fn)
            jax.block_until_ready(f(co, key, gains32, st))
            d_iters = 2 if n >= 1_000_000 else 5
            t0 = time.time()
            for _ in range(d_iters):
                jax.block_until_ready(f(co, key, gains32, st))
            d_us = (time.time() - t0) / d_iters * 1e6
            entry[f"decision_{label}_us"] = d_us
            _emit(f"massive_n{n}_decision_{label}", d_us,
                  f"per_client_ns={d_us * 1000 / n:.1f}")
        results["n"][n] = entry

    # composed 2D mesh: the FULL federated round (schedule sharded over
    # 'client', packed participants' local SGD over 'part') on one shared
    # (Dc, Dp) mesh — the fl/client_shard.py composition path. Smaller N
    # than the scheduling-only rows above because this leg materializes a
    # dataset and trains; what it watches is the round-loop throughput of
    # the composed mesh, where a regression in the shard_map plumbing
    # (operand pins, index-pack hand-off, psum aggregate) shows up as a
    # collapsed rounds/s long before any parity test times out. Same
    # shared-core caveat as above: flat vs sequential is expected here.
    from repro.data.synthetic import make_cifar10_like
    from repro.fl.engine import SimConfig, make_config_runner
    from repro.models.registry import make_model
    dc, dp = next((c, p) for c, p in ((4, 2), (2, 2), (2, 1), (1, 1))
                  if c * p <= n_dev)
    n2 = 96
    ds = make_cifar10_like(jax.random.PRNGKey(3), n_clients=n2,
                           per_client=32, n_test=64, h=8, w=8)
    sim2 = SimConfig(rounds=rounds, eval_every=rounds, m_cap=6, batch=8,
                     local_steps=2, eval_size=64, model="mlp",
                     client_shards=dc, participant_shards=dp)
    params = make_model("mlp", ds).init_fn(jax.random.PRNGKey(1))
    ch2 = ChannelConfig(n_clients=n2)
    scfg2 = SchedulerConfig(n_clients=n2, model_bits=32 * 50_000.0)
    sig2 = heterogeneous_sigmas(n2)
    runner2 = make_config_runner(ds, sim2, scfg2, ch2, sig2)
    key2 = jax.random.PRNGKey(4)
    t0 = time.time()
    jax.block_until_ready(runner2(params, key2))
    compile_wall = time.time() - t0
    t0 = time.time()
    jax.block_until_ready(runner2(params, key2))
    wall = time.time() - t0
    rps = rounds / wall
    results["mesh2d"] = {"mesh": [dc, dp], "n_clients": n2,
                         "rounds_per_sec": rps,
                         "compile_plus_first_run_s": compile_wall}
    _emit("massive_mesh2d", 1e6 / rps,
          f"rounds_per_sec={rps:.2f};mesh={dc}x{dp};devices={n_dev};"
          f"compile_s={compile_wall:.1f}")
    _dump("massive", results)
    return results


# ------------------------------------------------------------------ service

def bench_service(prof):
    """Multi-tenant online scheduler service: decisions/s and per-flush
    latency (p50/p99) vs tenant count, batch size, and bucket mix.

    The service (repro/service) serves the engines' per-round decision
    step online: requests carry instantaneous gains + raw selection draws,
    tenants are grouped into power-of-two N-buckets, and each bucket runs
    as ONE jit(vmap) step with donated queue state. This bench registers
    >= 1000 heterogeneous tenants across 3 N-buckets (each tenant its own
    V/lam/ell/Pmax and policy) and measures steady-state serving:

    * ``full`` — every tenant submits each round (throughput mode);
    * ``batch64`` — random 64-tenant batches (latency mode, after
      ``warmup(64)``: the pre-PR-8 p99 here was ~458 ms — random subsets
      split unevenly across buckets, so unseen power-of-two batch shapes
      kept compiling mid-measurement);
    * ``small100`` — a 100-tenant service, same mix (tenant-count axis);
    * ``smallflush`` — 1-8 request flushes after ``warmup()`` (the
      latency path: staged arenas + pre-compiled batch shapes — the
      pre-warmup pathology was ~half-second p99 from mid-measurement
      power-of-two shape compiles);
    * ``evict_churn`` — LRU evict -> spill -> reload -> serve cycles
      (tenant lifecycle: host row pull, bucket compaction +
      re-materialization, readmission).

    JSON artifact: benchmarks/out/service.json. Latency is wall-clock per
    ``flush()`` (host batching + jit dispatch + device step + host slice),
    so it is an end-to-end number, not a kernel time — but each scenario
    now also carries ``segments_ms``, the per-group attribution of that
    wall into its three host segments (arena staging / async dispatch /
    result pull) read from the service's own flush-segment histograms
    (``repro.obs``), so "flush got slower" decomposes instead of being a
    lump sum.

    The ``obs_overhead`` leg measures what the telemetry itself costs:
    two identical services — one telemetry-on, one off — serve the SAME
    request stream with interleaved arms (so machine drift decorrelates
    from the arm), and ``p50_ratio`` (enabled/disabled flush p50) is
    gated < 5% by benchmarks/compare.py against the committed baseline.
    """
    import jax  # noqa: F401  (ensures backend init outside the timing)
    from repro.service import SchedulerService
    from repro.service.demo import (DEFAULT_MIX, demo_request,
                                    lifecycle_cycle, register_demo_tenants)

    rng = np.random.default_rng(0)
    mix = DEFAULT_MIX   # buckets 32 / 128 / 512, >= 1000 tenants

    def build(counts_scale=1.0):
        svc = SchedulerService(telemetry=True)
        return svc, register_demo_tenants(svc, rng, mix,
                                          scale=counts_scale)

    SEGMENTS = (("stage", "service_flush_stage_seconds"),
                ("dispatch", "service_flush_dispatch_seconds"),
                ("pull", "service_flush_pull_seconds"))

    def seg_cursor(svc):
        """(sum, count) per flush segment — deltas attribute a window."""
        reg = svc.obs.registry
        return {k: (reg.histogram(nm).total, reg.histogram(nm).count)
                for k, nm in SEGMENTS}

    def seg_means_ms(svc, before):
        cur = seg_cursor(svc)
        return {f"{k}_ms": 1e3 * (cur[k][0] - before[k][0])
                / max(1, cur[k][1] - before[k][1]) for k in cur}

    def drive(svc, tenants, n_flushes, batch=None):
        walls, served = [], 0
        for _ in range(n_flushes):
            subset = tenants if batch is None else [
                tenants[j] for j in rng.choice(len(tenants), batch,
                                               replace=False)]
            reqs = [demo_request(rng, *t) for t in subset]
            t0 = time.time()
            for name, gains, raw in reqs:
                svc.submit(name, gains, raw=raw)
            svc.flush(log=False)
            walls.append(time.time() - t0)
            served += len(reqs)
        return served, walls

    flushes = max(6, min(20, prof.rounds // 2))
    results = {"mix": [{"n": n, "tenants": c, "policy": p}
                       for n, c, p in mix],
               "flushes": flushes, "scenarios": {}}
    svc, tenants = build()
    svc.warmup(max_batch=64)   # pre-compile every random-subset batch shape
    scenarios = [("full", svc, tenants, None),
                 ("batch64", svc, tenants, 64)]
    svc100, tenants100 = build(counts_scale=0.1)
    scenarios.append(("small100", svc100, tenants100, None))
    for label, s, t, batch in scenarios:
        # warm the compiled buckets; random small batches need several
        # passes to visit the power-of-two batch shapes they will draw
        drive(s, t, 1 if batch is None else 6, batch=batch)
        cursor = seg_cursor(s)
        served, walls = drive(s, t, flushes, batch=batch)
        walls_ms = np.sort(np.asarray(walls)) * 1e3
        dps = served / float(np.sum(walls))
        entry = {
            "tenants": len(t), "requests": served,
            "decisions_per_sec": dps,
            "p50_ms": float(np.percentile(walls_ms, 50)),
            "p99_ms": float(np.percentile(walls_ms, 99)),
            "segments_ms": seg_means_ms(s, cursor),
        }
        results["scenarios"][label] = entry
        _emit(f"service_{label}", 1e6 * float(np.sum(walls)) / served,
              f"decisions_per_sec={dps:.0f};tenants={len(t)};"
              f"p50_ms={entry['p50_ms']:.1f};p99_ms={entry['p99_ms']:.1f}")

    # smallflush: tiny (1-8 request) flushes against the FULL service —
    # the interactive-latency path. warmup() pre-compiles every bucket's
    # power-of-two batch shapes with all-sentinel batches (state bitwise
    # untouched), so the measured p99 is steady-state staging + dispatch,
    # not a mid-measurement shape compile.
    svc.warmup(max_batch=8)
    cursor = seg_cursor(svc)
    walls, served = [], 0
    for _ in range(max(40, 4 * flushes)):
        b = int(rng.integers(1, 9))
        subset = [tenants[j] for j in rng.choice(len(tenants), b,
                                                 replace=False)]
        reqs = [demo_request(rng, *t) for t in subset]
        t0 = time.time()
        for name, gains, raw in reqs:
            svc.submit(name, gains, raw=raw)
        svc.flush(log=False)
        walls.append(time.time() - t0)
        served += b
    walls_ms = np.sort(np.asarray(walls)) * 1e3
    dps = served / float(np.sum(walls))
    entry = {
        "tenants": len(tenants), "requests": served, "flushes": len(walls),
        "decisions_per_sec": dps,
        "p50_ms": float(np.percentile(walls_ms, 50)),
        "p99_ms": float(np.percentile(walls_ms, 99)),
        "segments_ms": seg_means_ms(svc, cursor),
    }
    results["scenarios"]["smallflush"] = entry
    _emit("service_smallflush", 1e6 * float(np.sum(walls)) / served,
          f"decisions_per_sec={dps:.0f};"
          f"p50_ms={entry['p50_ms']:.2f};p99_ms={entry['p99_ms']:.2f}")

    # evict_churn: full tenant-lifecycle cycles on the 100-tenant service
    # (evict_lru -> spill -> reload -> serve one round). The jnp bucket
    # steps are shape-polymorphic jit functions, so after the warm cycles
    # the churn is pure host lifecycle work + one 1-row serve, no
    # recompilation.
    churn_rng = np.random.default_rng(3)
    by_name = {nm: (n, p) for nm, n, p in tenants100}
    for _ in range(3):
        lifecycle_cycle(svc100, churn_rng, by_name)
    n_cycles = max(10, flushes)
    t0 = time.time()
    for _ in range(n_cycles):
        lifecycle_cycle(svc100, churn_rng, by_name)
    wall = time.time() - t0
    cps = n_cycles / wall
    results["scenarios"]["evict_churn"] = {
        "tenants": len(tenants100), "cycles": n_cycles,
        "cycles_per_sec": cps,
        "ms_per_cycle": 1e3 * wall / n_cycles,
    }
    _emit("service_evict_churn", 1e6 * wall / n_cycles,
          f"cycles_per_sec={cps:.1f};tenants={len(tenants100)}")

    # obs_overhead: what does telemetry itself cost on the flush path?
    # Two identical 100-tenant services — one telemetry-on, one off —
    # serve the SAME request stream; arms are interleaved (and alternate
    # order) so machine drift decorrelates from the arm. The committed
    # baseline pins p50_ratio ~ 1.0 and compare.py gates it < 5%.
    svc_on = SchedulerService(telemetry=True)
    t_on = register_demo_tenants(svc_on, np.random.default_rng(7), mix,
                                 scale=0.1)
    svc_off = SchedulerService(telemetry=False)
    register_demo_tenants(svc_off, np.random.default_rng(7), mix,
                          scale=0.1)
    svc_on.warmup(max_batch=16)
    svc_off.warmup(max_batch=16)
    req_rng = np.random.default_rng(11)
    walls_on, walls_off = [], []
    n_obs = max(40, 4 * flushes)
    for i in range(n_obs):
        subset = [t_on[j] for j in req_rng.choice(len(t_on), 16,
                                                  replace=False)]
        reqs = [demo_request(req_rng, *t) for t in subset]
        arms = [(svc_on, walls_on), (svc_off, walls_off)]
        if i % 2:
            arms.reverse()
        for s, walls in arms:
            t0 = time.time()
            for name, gains, raw in reqs:
                s.submit(name, gains, raw=raw)
            s.flush(log=False)
            walls.append(time.time() - t0)
    p50_on = float(np.percentile(np.asarray(walls_on) * 1e3, 50))
    p50_off = float(np.percentile(np.asarray(walls_off) * 1e3, 50))
    ratio = p50_on / p50_off
    results["scenarios"]["obs_overhead"] = {
        "tenants": len(t_on), "flushes": n_obs, "batch": 16,
        "p50_ms_enabled": p50_on, "p50_ms_disabled": p50_off,
        "p50_ratio": ratio,
    }
    _emit("service_obs_overhead", 1e3 * p50_on,
          f"p50_ratio={ratio:.3f};on_ms={p50_on:.2f};off_ms={p50_off:.2f}")
    _dump("service", results)
    return results


# ------------------------------------------------------------------ kernels

def bench_kernels(prof):
    """us/call for the paper-core scheduler solve (jnp path) and the fused
    decision megakernel vs the stitched decision it replaces.

    The fused leg times the FULL per-round decision (Theorem-2 solve +
    Bernoulli selection + Eq. 9 queue update + accounting) as one jitted
    step, stitched (``decision_step`` + coefficient-driven policy) vs the
    ``kernels/decision_fused.py`` megakernel drop-in, at N up to 10^6 —
    the bitwise-parity pair tests/test_decision_fused.py pins. Off-TPU the
    kernel runs in interpret mode, so its absolute time documents the
    (expected, large) CPU validation penalty, not kernel speed; the
    stitched row is the meaningful CPU number and the regression gate
    tracks both (benchmarks/compare.py).

    JSON artifact: benchmarks/out/kernels.json.
    """
    import jax
    import jax.numpy as jnp
    from repro.core import ChannelConfig, SchedulerConfig, make_policy
    from repro.core.policies import init_policy_state
    from repro.core.scheduler import solve_round
    from repro.fl.decision import (decision_coeffs, decision_step,
                                   make_fused_decision)

    results = {"solve": {}, "decision": {}}
    for n in (100, 3597, 100_000):
        ch = ChannelConfig(n_clients=n)
        cfg = SchedulerConfig(n_clients=n, model_bits=32 * 555178.0)
        gains = jnp.exp(jax.random.normal(jax.random.PRNGKey(0), (n,)))
        z = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (n,)))
        f = jax.jit(lambda g, z: solve_round(g, z, cfg, ch))
        jax.block_until_ready(f(gains, z))
        t0 = time.time()
        iters = 50
        for _ in range(iters):
            jax.block_until_ready(f(gains, z))
        us = (time.time() - t0) / iters * 1e6
        results["solve"][n] = us
        _emit(f"kernel_solve_round_n{n}", us,
              f"per_client_ns={us * 1000 / n:.1f}")

    mode = "compiled" if jax.default_backend() == "tpu" else "interpret"
    for n in (10_000, 100_000, 1_000_000):
        ch = ChannelConfig(n_clients=n)
        scfg = SchedulerConfig(n_clients=n, model_bits=32 * 555178.0)
        co = decision_coeffs(scfg, ch)
        gains = jnp.exp(jax.random.normal(jax.random.PRNGKey(0),
                                          (n,))).astype(jnp.float32)
        st = init_policy_state("proposed", n)._replace(
            z=jnp.abs(jax.random.normal(jax.random.PRNGKey(1),
                                        (n,))).astype(jnp.float32) * 10)
        key = jax.random.PRNGKey(2)

        def stitched(co, key, gains, st):
            step = make_policy("proposed", scfg, ch, coeffs=co.solve)
            return decision_step(step, co.acct, key, gains, st)

        def fused(co, key, gains, st):
            return make_fused_decision(scfg, co)(None, None, key, gains, st)

        entry = {"mode": mode}
        for label, fn in (("stitched", stitched), ("fused", fused)):
            f = jax.jit(fn)
            jax.block_until_ready(f(co, key, gains, st))
            iters = 2 if (n >= 1_000_000 and mode == "interpret") else 5
            t0 = time.time()
            for _ in range(iters):
                jax.block_until_ready(f(co, key, gains, st))
            us = (time.time() - t0) / iters * 1e6
            entry[f"{label}_us"] = us
            _emit(f"kernel_decision_{label}_n{n}", us,
                  f"per_client_ns={us * 1000 / n:.1f};mode="
                  f"{'compiled' if label == 'stitched' else mode}")
        entry["fused_over_stitched"] = (entry["fused_us"]
                                        / entry["stitched_us"])
        results["decision"][n] = entry
    _dump("kernels", results)
    return results


BENCHES = {
    "engine": bench_engine,
    "grid": bench_grid,
    "tournament": bench_tournament,
    "round": bench_round,
    "massive": bench_massive,
    "service": bench_service,
    "fig2_cifar": bench_fig2_cifar,
    "fig3_lambda": bench_fig3_lambda,
    "fig4_femnist": bench_fig4_femnist,
    "fig5_power": bench_fig5_power,
    "roofline": bench_roofline,
    "kernels": bench_kernels,
}


def main(argv=None):
    enable_compile_cache()
    from benchmarks.figures import FULL, SMOKE, BenchProfile

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default="")
    args = ap.parse_args(argv)
    prof = SMOKE if args.smoke else (FULL if args.full else BenchProfile())

    only = set(args.only.split(",")) if args.only else None
    if only:
        unknown = only - set(BENCHES)
        if unknown:
            ap.error(f"unknown benchmarks {sorted(unknown)} "
                     f"(available: {sorted(BENCHES)})")
    print("name,us_per_call,derived")
    fig2 = None
    failed = []
    for name, fn in BENCHES.items():
        if only and name not in only:
            continue
        try:
            if name == "fig3_lambda":
                fn(prof, fig2)
            elif name == "fig2_cifar":
                fig2 = fn(prof)
            else:
                fn(prof)
        except Exception as e:  # noqa: BLE001
            _emit(name, -1.0, f"ERROR:{e!r}")
            failed.append(name)
    if failed:
        # a crashed bench must fail CI's smoke job, not hide behind the
        # other benches' successful JSON dumps
        raise SystemExit(f"benchmarks failed: {failed}")


if __name__ == "__main__":
    main()
