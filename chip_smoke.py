"""Smoke run of the system's main paths on a TPU chip, at the paper's widths.

    python chip_smoke.py               # one chip: engine, service, scheduling
    python chip_smoke.py --four-chips  # four chips: the sharded paths only

One chip, three phases, each serving the same work through the fused Pallas
decision kernel (``solver="pallas_fused"``) and through the stitched jnp
decision, and holding the two to each other:

* engine — the paper's Section VI-A experiment (``configs/cifar10_cnn.py``:
  N = 100 heterogeneous clients, the CNN at 32/64/120 on 32x32x3, batch 32,
  I = 10, gamma = 0.01, V = 1000, lambda = 10, ell = 32 * 555,178) on the
  full ``make_cifar10_like`` data, a few rounds through ``run_simulation``;
* service — ``SchedulerService`` over ``service/demo.py::DEFAULT_MIX``
  (1,020 tenants in buckets of 32/128/512): ``warmup()``, then a few
  flushes of every tenant, none of which may compile;
* scheduling — ``make_schedule_runner`` at N = 10^6 for a few rounds.

Integer results (participant counts, selections) must match exactly, floats
to ``SOLVER_RTOL``; where a service lane's optimum ties the Pmax boundary,
its two powers are held to the objective instead (``_check_decisions``).
Each phase also shows that the decision kernel it ran was compiled for the
chip (``tpu_custom_call`` in the compiled program), not interpreted.

``--four-chips`` runs only what exists across chips: the engine round on the
``('client', 'part')`` = (2, 2) mesh and ``make_schedule_runner`` with
``client_shards=4`` at N = 10^6, each against the same work on one chip.
Integers must match exactly and float accounting to ``MESH_RTOL`` (a few
ulp), and the sharded outputs must live on four devices.

Each phase prints one line: XLA compile seconds, wall seconds and its
largest deviation. These are smoke readings, not benchmark metrics. Any
failure raises, so the script exits non-zero before the last line, which on
success is exactly ``{"ok": true, "device": {...}}``. Without a TPU the script
exits non-zero at once and prints no result.

Everything runs in this one process, which holds the chip; it starts no
other. The compile cache follows ``repro.launch.compile_cache``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.configs.cifar10_cnn import CONFIG  # noqa: E402
from repro.core import heterogeneous_sigmas  # noqa: E402
from repro.core.policies import init_policy_state  # noqa: E402
from repro.core.scheduler import SolveCoeffs, solve_coeffs  # noqa: E402
from repro.data.synthetic import make_cifar10_like  # noqa: E402
from repro.fl import SimConfig, make_schedule_runner  # noqa: E402
from repro.fl.decision import decision_coeffs  # noqa: E402
from repro.fl.engine import (history_from_trajectory,  # noqa: E402
                             make_config_runner, resolve_fused_decision)
from repro.fl.simulation import run_simulation  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models.registry import make_model  # noqa: E402
from repro.service import SchedulerService, make_bucket_step  # noqa: E402
from repro.service.demo import (DEFAULT_MIX, demo_request,  # noqa: E402
                                register_demo_tenants)

# pallas_fused vs jnp on the chip: Mosaic and XLA may round the solve's
# exp/log chains differently, so floats agree to round-off, not bitwise
SOLVER_RTOL = 1e-5
# one chip vs four: the same per-lane programs and the fixed-block
# accounting association on every mesh, so float accounting to a few ulp
MESH_RTOL = 4 * float(np.finfo(np.float32).eps)
LAMBDA = 10.0
KERNEL_MARK = "tpu_custom_call"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@dataclasses.dataclass(frozen=True)
class EngineSize:
    """The engine phase's experiment; defaults are the paper's VI-A."""

    n_clients: int = CONFIG.n_clients
    per_client: int = 500
    n_test: int = 10_000
    hw: int = CONFIG.cnn.height
    conv1: int = CONFIG.cnn.conv1
    conv2: int = CONFIG.cnn.conv2
    hidden: int = CONFIG.cnn.hidden
    batch: int = CONFIG.batch
    local_steps: int = CONFIG.local_steps
    rounds: int = 5
    eval_every: int = 2
    eval_size: int = 2000
    m_cap: int = 32


class CompileWatch:
    """Counts and times XLA compiles (JAX's backend-compile events)."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0

    def _on_event(self, event, duration, **_):
        if event == _COMPILE_EVENT:
            self.count += 1
            self.seconds += duration

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.wall = time.perf_counter() - self._t0
        jax.monitoring.unregister_event_duration_listener(self._on_event)


def _dev(a, b) -> float:
    """Largest |a - b|, relative to the largest |b|."""
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def _check_close(name, a, b, rtol) -> float:
    d = _dev(a, b)
    if not d <= rtol:
        i = int(np.argmax(np.abs(np.ravel(a) - np.ravel(b))))
        raise AssertionError(
            f"{name}: deviation {d:.3e} exceeds {rtol:.1e} (worst at {i}: "
            f"{np.ravel(a)[i]!r} vs {np.ravel(b)[i]!r})")
    return d


def _check_equal(name, a, b) -> None:
    if not np.array_equal(np.asarray(a), np.asarray(b)):
        raise AssertionError(f"{name}: {np.asarray(a)} != {np.asarray(b)}")


def _has_kernel(fn, *args) -> bool:
    """Whether ``jit(fn)`` at these arguments holds a chip kernel."""
    return KERNEL_MARK in jax.jit(fn).lower(*args).compile().as_text()


def _decision_has_kernel(sim, scfg, ch, n) -> bool:
    """The engines' fused decision layer, as they build it, at (N,)."""
    fd = resolve_fused_decision(sim, scfg, decision_coeffs(scfg, ch))
    return _has_kernel(lambda k, g, s: fd(None, None, k, g, s),
                       jax.random.PRNGKey(0), jnp.ones((n,), jnp.float32),
                       init_policy_state(sim.policy, n))


def _paper_configs(n):
    ch = dataclasses.replace(CONFIG.channel(), n_clients=n)
    scfg = dataclasses.replace(CONFIG.scheduler(LAMBDA), n_clients=n)
    return scfg, ch


def _engine_setup(size: EngineSize, seed: int):
    ds = make_cifar10_like(jax.random.PRNGKey(seed), n_clients=size.n_clients,
                           per_client=size.per_client, n_test=size.n_test,
                           h=size.hw, w=size.hw)
    model_params = (("conv1", size.conv1), ("conv2", size.conv2),
                    ("hidden", size.hidden))
    params = make_model("cnn", ds, **dict(model_params)).init_fn(
        jax.random.PRNGKey(seed + 1))
    scfg, ch = _paper_configs(size.n_clients)
    base = dict(rounds=size.rounds, eval_every=size.eval_every,
                eval_size=size.eval_size, gamma=CONFIG.gamma,
                local_steps=size.local_steps, batch=size.batch,
                m_cap=size.m_cap, model="cnn", model_params=model_params,
                policy="proposed")
    return ds, params, scfg, ch, heterogeneous_sigmas(size.n_clients), base


def engine_phase(size: EngineSize = EngineSize(), seed: int = 0) -> dict:
    """``run_simulation`` with the fused kernel vs the stitched decision."""
    ds, params, scfg, ch, sig, base = _engine_setup(size, seed)
    key = jax.random.PRNGKey(seed + 2)
    hists = {}
    with CompileWatch() as cw:
        for solver in ("pallas_fused", "jnp"):
            hists[solver] = run_simulation(key, params, ds,
                                           SimConfig(solver=solver, **base),
                                           scfg, ch, sig)
    fused, ref = hists["pallas_fused"], hists["jnp"]
    _check_equal("engine n_selected", fused["n_selected"], ref["n_selected"])
    dev = max(_check_close(f"engine {k}", fused[k], ref[k], SOLVER_RTOL)
              for k in ("comm_time", "avg_power"))
    for h in (fused, ref):
        if not np.all(np.isfinite(h["test_acc"])):
            raise AssertionError(f"engine test_acc not finite: {h}")
    return dict(phase="engine", n_clients=size.n_clients, rounds=size.rounds,
                compile_s=cw.seconds, wall_s=cw.wall, max_dev=dev,
                n_selected=fused["n_selected"].tolist(),
                test_acc=fused["test_acc"].tolist(),
                chip_kernel=_decision_has_kernel(
                    SimConfig(solver="pallas_fused", **base), scfg, ch,
                    size.n_clients))


def _objective(q, p, gains, z, c: SolveCoeffs):
    """Eq. (15)'s per-lane drift-plus-penalty f(q, P), in float64."""
    c = SolveCoeffs(*(np.float64(x) for x in c))
    rate = c.bw * np.log2(1.0 + gains * p / c.n0)
    return (c.v * (1.0 / (c.n * q) + c.lle * q / np.maximum(rate, 1e-12))
            + z * (p * q - c.p_bar))


def _check_own_accounting(name, d, gains, c: SolveCoeffs, ell) -> float:
    """A decision's Eq. 8 time and power against its own (sel, q, p)."""
    p = d.p.astype(np.float64)
    rate = np.float64(c.bw) * np.log2(1.0 + gains * p / np.float64(c.n0))
    t_comm = np.sum(np.where(d.sel, ell / np.maximum(rate, 1e-9), 0.0))
    return max(_check_close(f"{name} t_comm", d.t_comm, t_comm, SOLVER_RTOL),
               _check_close(f"{name} power", d.power,
                            np.sum(p * d.q.astype(np.float64)), SOLVER_RTOL))


def _check_decisions(name, a, b, gains, z, spec) -> tuple:
    """Tenant ``name``'s fused decision ``a`` against the jnp one ``b``,
    both made from the same state. Returns (largest deviation, ties).

    Selections are exact and q agrees to ``SOLVER_RTOL``. P does too,
    except on a tie: where the interior optimum lies just below Pmax the
    objective is flat in P (and q stationary), so one path may keep the
    interior candidate and the other the boundary on a last-bit difference
    in exp/log. There the two decisions must reach the same Eq. (15)
    objective, and each its own Eq. 8 accounting; elsewhere time and power
    agree as they are."""
    _check_equal(f"service {name} sel", a.sel, b.sel)
    _check_equal(f"service {name} n_sel", a.n_sel, b.n_sel)
    c = solve_coeffs(spec.scfg, spec.ch)
    ties = (a.p == c.p_max) != (b.p == c.p_max)
    dev = max(_check_close(f"service {name} q", a.q, b.q, SOLVER_RTOL),
              _check_close(f"service {name} p", np.where(ties, b.p, a.p),
                           b.p, SOLVER_RTOL))
    if spec.policy == "proposed":
        g, zz = gains.astype(np.float64), z.astype(np.float64)
        dev = max(dev, _check_close(
            f"service {name} objective",
            _objective(a.q.astype(np.float64), a.p.astype(np.float64),
                       g, zz, c),
            _objective(b.q.astype(np.float64), b.p.astype(np.float64),
                       g, zz, c), SOLVER_RTOL))
    if ties.any():
        for d in (a, b):
            dev = max(dev, _check_own_accounting(
                f"service {name}", d, gains.astype(np.float64), c,
                spec.scfg.model_bits))
    else:
        for f in ("t_comm", "power"):
            dev = max(dev, _check_close(f"service {name} {f}", getattr(a, f),
                                        getattr(b, f), SOLVER_RTOL))
    return dev, int(ties.sum())


def service_phase(mix=DEFAULT_MIX, flushes: int = 3, seed: int = 0) -> dict:
    """The multi-tenant service, fused vs jnp, on one request stream.

    Before each flush the jnp service takes the fused one's state
    (``snapshot``/``restore``), so every flush compares the two solvers on
    one input: a tie (see :func:`_check_decisions`) moves that lane's queue
    by P q, which would otherwise carry into later flushes."""
    services, tenants = {}, None
    with CompileWatch() as warm:
        for solver in ("pallas_fused", "jnp"):
            svc = SchedulerService(solver=solver, log_requests=False,
                                   telemetry=True)
            tenants = register_demo_tenants(svc, np.random.default_rng(seed),
                                            mix)
            svc.warmup(max_batch=max(count for _, count, _ in mix))
            services[solver] = svc
    fused_svc, ref_svc = services["pallas_fused"], services["jnp"]
    misses = {s: v.obs.compiles.misses_total() for s, v in services.items()}
    rng = np.random.default_rng(seed + 1)
    dev, ties = 0.0, 0
    with CompileWatch() as serve:
        for _ in range(flushes):
            snap = fused_svc.snapshot()
            ref_svc.restore(snap)
            reqs = [demo_request(rng, *t) for t in tenants]
            out = {}
            for solver, svc in services.items():
                for name, gains, raw in reqs:
                    svc.submit(name, gains, raw=raw)
                out[solver] = svc.flush()
            for name, gains, _ in reqs:
                spec = fused_svc.store.spec(name)
                z = snap[spec.bucket.as_string()].z[
                    fused_svc.store.row(name), :spec.n]
                d, t = _check_decisions(name, out["pallas_fused"][name],
                                        out["jnp"][name], gains, z, spec)
                dev, ties = max(dev, d), ties + t
    for solver, svc in services.items():
        if svc.obs.compiles.misses_total() != misses[solver]:
            raise AssertionError(f"service ({solver}) compiled after warmup")
    if serve.count:
        raise AssertionError(f"{serve.count} compiles while serving after "
                             "warmup()")
    fused = [(k, b) for k, b in fused_svc.store.buckets().items()
             if k.policy == "proposed"]
    kernel = bool(fused) and all(_bucket_has_kernel(k, b) for k, b in fused)
    return dict(phase="service", tenants=len(tenants), flushes=flushes,
                compile_s=warm.seconds, wall_s=warm.wall + serve.wall,
                serve_compiles=serve.count, max_dev=dev, ties=ties,
                chip_kernel=kernel)


def _bucket_has_kernel(bkey, bucket) -> bool:
    """The service's fused bucket step, at the batch shape of a flush that
    serves every tenant of the bucket."""
    step = make_bucket_step(bkey.policy, bkey.n_bucket, bkey.acct_len,
                            bkey.guarantee_one, fused=True)
    b = 1 << max(0, bucket.size - 1).bit_length()
    return KERNEL_MARK in step.lower(
        bucket.state, bucket.coeffs, bucket.acct, bucket.n_real,
        np.full((b,), bucket.size, np.int32),
        np.ones((b, bkey.n_bucket), np.float32),
        np.full((b, bkey.n_bucket), 2.0, np.float32)).compile().as_text()


def _schedule(n, rounds, seed, **kw):
    scfg, ch = _paper_configs(n)
    runner = make_schedule_runner(heterogeneous_sigmas(n), scfg, ch,
                                  rounds=rounds, **kw)
    return jax.block_until_ready(runner(jax.random.PRNGKey(seed)))


def schedule_phase(n: int = 1_000_000, rounds: int = 3, seed: int = 0) -> dict:
    """Scheduling-only rounds at N clients, fused kernel vs stitched."""
    with CompileWatch() as cw:
        fused = _schedule(n, rounds, seed, solver="pallas_fused")
        ref = _schedule(n, rounds, seed, solver="jnp")
    _check_equal("schedule n_sel", fused[2], ref[2])
    dev = max(_check_close("schedule t_comm", fused[0], ref[0], SOLVER_RTOL),
              _check_close("schedule power", fused[1], ref[1], SOLVER_RTOL))
    scfg, ch = _paper_configs(n)
    return dict(phase="schedule", n_clients=n, rounds=rounds,
                compile_s=cw.seconds, wall_s=cw.wall, max_dev=dev,
                n_sel=np.asarray(fused[2]).tolist(),
                chip_kernel=_decision_has_kernel(
                    SimConfig(solver="pallas_fused"), scfg, ch, n))


def _n_devices(x) -> int:
    return len(x.sharding.device_set)


def four_chip_engine_phase(size: EngineSize = EngineSize(),
                           seed: int = 0) -> dict:
    """The (client, part) = (2, 2) engine round vs the same on one chip."""
    ds, params, scfg, ch, sig, base = _engine_setup(size, seed)
    key = jax.random.PRNGKey(seed + 2)
    outs = {}
    with CompileWatch() as cw:
        for mesh in ((0, 0), (2, 2)):
            sim = SimConfig(solver="pallas_fused", client_shards=mesh[0],
                            participant_shards=mesh[1], **base)
            outs[mesh] = jax.block_until_ready(
                make_config_runner(ds, sim, scfg, ch, sig)(params, key))
    spread = _n_devices(outs[(2, 2)][0])
    if spread != 4:
        raise AssertionError(f"(2, 2) round ran on {spread} device(s)")
    one, four = (history_from_trajectory(size.rounds, size.eval_every,
                                         size.n_clients, *outs[m])
                 for m in ((0, 0), (2, 2)))
    _check_equal("mesh engine n_selected", four["n_selected"],
                 one["n_selected"])
    dev = max(_check_close(f"mesh engine {k}", four[k], one[k], MESH_RTOL)
              for k in ("comm_time", "avg_power"))
    if not np.all(np.isfinite(four["test_acc"])):
        raise AssertionError(f"mesh engine test_acc not finite: {four}")
    return dict(phase="engine_2x2", devices=spread, compile_s=cw.seconds,
                wall_s=cw.wall, max_dev=dev,
                n_selected=four["n_selected"].tolist(),
                test_acc_one=one["test_acc"].tolist(),
                test_acc_four=four["test_acc"].tolist())


def four_chip_schedule_phase(n: int = 1_000_000, rounds: int = 3,
                             seed: int = 0) -> dict:
    """``client_shards=4`` scheduling vs the same on one chip."""
    with CompileWatch() as cw:
        one = _schedule(n, rounds, seed, solver="pallas_fused")
        four = _schedule(n, rounds, seed, solver="pallas_fused",
                         client_shards=4)
    spread = _n_devices(four[0])
    if spread != 4:
        raise AssertionError(f"client_shards=4 ran on {spread} device(s)")
    _check_equal("mesh schedule n_sel", four[2], one[2])
    dev = max(_check_close("mesh schedule t_comm", four[0], one[0],
                           MESH_RTOL),
              _check_close("mesh schedule power", four[1], one[1],
                           MESH_RTOL))
    return dict(phase="schedule_x4", n_clients=n, devices=spread,
                compile_s=cw.seconds, wall_s=cw.wall, max_dev=dev,
                n_sel=np.asarray(four[2]).tolist())


def _report(res: dict) -> None:
    print(" ".join(f"{k}={v:.6g}" if isinstance(v, float) else f"{k}={v}"
                   for k, v in res.items()), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded paths, on four chips")
    args = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: no TPU (JAX backend is "
              f"{jax.default_backend()!r})", file=sys.stderr)
        return 1
    enable_compile_cache()
    want = 4 if args.four_chips else 1
    if len(jax.devices()) < want:
        print(f"chip_smoke: needs {want} chips, found {len(jax.devices())}",
              file=sys.stderr)
        return 1
    if args.four_chips:
        phases = (four_chip_engine_phase, four_chip_schedule_phase)
    else:
        phases = (engine_phase, service_phase, schedule_phase)
    for phase in phases:
        res = phase()
        _report(res)
        if res.get("chip_kernel") is False:
            raise AssertionError(f"{res['phase']}: the decision kernel was "
                                 "not compiled for the chip")
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
