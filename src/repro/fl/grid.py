"""Device-sharded scenario grid: channel x sigma-dist x policy x seed.

The paper's headline claim (Figs. 3-6) is a comparison across *wireless
scenarios* — homogeneous vs heterogeneous Rayleigh scales, i.i.d. vs
non-i.i.d. data — and the related-work baselines (update-aware, channel-
greedy, AoI-capped) multiply the comparison space further. This module runs
that whole space as ONE compiled call:

* :class:`GridSpec` declares the grid — registered channel models (with
  params), named sigma distributions, registered policies, seeds.
* :func:`make_grid_runner` compiles the grid once into a single
  ``jit(shard_map(...))``: configs are grouped by (channel, policy) cell,
  each cell binds its channel step and policy statically and runs its
  (sigma x seed) configs under ``lax.map``, and the config axis is sharded
  across devices (the 8-virtual-CPU-device idiom from ``scripts/test.sh``
  makes this testable in CI). Per config, the full simulated trajectory —
  fading draws -> selection policy -> local SGD -> Algorithm-1 aggregation
  -> TDMA accounting — runs through the exact per-config program of
  :func:`repro.fl.engine.run_simulation_scan` (``run_config_chunks``).
* Uneven grids are padded per cell up to a multiple of the device count by
  repeating the last config; the padding is sliced off after the gather.

Static per-cell binding (rather than a ``lax.switch`` over channel/policy
ids) is deliberate: a config never pays for a branch it discards, and —
more fundamentally — XLA compiles the *same* round math to different
float32 bits when it sits inside a multi-branch conditional, which would
break the grid's parity contract. As built, per-config grid trajectories
are bitwise-identical to running ``run_simulation_scan`` on that config
alone (same trace, same key-split order) — ``tests/test_grid.py`` asserts
exact equality. The price is one ``lax.map`` per (channel, policy) cell:
cells execute sequentially, so device parallelism lives on the
sigma x seed axis within each cell.

The bitwise contract holds per mesh: changing the DEVICE COUNT changes the
per-device ``lax.map`` trip count, and XLA generates (ulp-level) different
code for a trip-1 loop than a trip-6 one — across device counts results
agree to ~1 ulp, not to the bit.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.core import ChannelConfig, SchedulerConfig, resolve_sigmas
from repro.core.channel import CHANNEL_MODELS
from repro.core.policies import POLICIES, init_policy_state, make_policy
from repro.data.synthetic import FederatedDataset
from repro.fl.decision import decision_coeffs, uses_fused
from repro.fl.engine import (CHANNEL_INIT_TAG, SimConfig, eval_rounds,
                             make_eval_fn, make_round_core, run_config_chunks)
from repro.fl.sharding import shard_map


def _normalize(entries) -> Tuple[Tuple[str, tuple], ...]:
    """("name" | ("name", ((param, value), ...))) -> canonical pairs."""
    out = []
    for e in entries:
        if isinstance(e, str):
            out.append((e, ()))
        else:
            name, params = e
            out.append((name, tuple(params)))
    return tuple(out)


@dataclasses.dataclass(frozen=True)
class GridSpec:
    """Declarative scenario grid (the cross product of the four axes).

    ``channels`` and ``policies`` entries are registry names, optionally
    paired with params: ``("gauss_markov", (("rho", 0.9),))``.
    ``sigma_dists`` entries are named distributions ("homogeneous" |
    "heterogeneous"); explicit (N,) arrays are accepted too.
    """

    channels: tuple = (("rayleigh", ()),)
    sigma_dists: tuple = ("heterogeneous",)
    policies: tuple = (("proposed", ()),)
    seeds: tuple = (0,)
    # population scenarios (repro.fl.population param tuples, e.g.
    # ``((("p_fail", 0.25),), ())`` — the empty entry is the degenerate
    # all-active scenario). The default () keeps the grid population-free
    # and its compiled program byte-identical to the pre-population grid;
    # a non-empty tuple adds a population axis between channels and
    # sigma_dists in every run_grid output array.
    populations: tuple = ()

    def channel_entries(self):
        return _normalize(self.channels)

    def policy_entries(self):
        return _normalize(self.policies)

    def population_entries(self):
        return tuple(tuple(p) for p in self.populations)

    @property
    def shape(self) -> Tuple[int, int, int, int]:
        return (len(self.channels), len(self.sigma_dists),
                len(self.policies), len(self.seeds))

    @property
    def size(self) -> int:
        c, s, p, k = self.shape
        return c * s * p * k * max(1, len(self.populations))

    def cells(self):
        """One compiled ``lax.map`` body each: (channel_idx, policy_idx)
        pairs on a population-free grid, (channel_idx, population_idx,
        policy_idx) triples when ``populations`` is set."""
        if self.populations:
            return list(itertools.product(range(len(self.channels)),
                                          range(len(self.populations)),
                                          range(len(self.policies))))
        return list(itertools.product(range(len(self.channels)),
                                      range(len(self.policies))))

    def validate(self):
        for name, _ in self.channel_entries():
            if name not in CHANNEL_MODELS:
                raise ValueError(f"unknown channel model {name!r} "
                                 f"(registered: {sorted(CHANNEL_MODELS)})")
        for name, _ in self.policy_entries():
            if name not in POLICIES:
                raise ValueError(f"unknown policy {name!r} "
                                 f"(registered: {sorted(POLICIES)})")
        if self.populations:
            from repro.fl.population import population_config
            for p in self.population_entries():
                population_config(p)  # raises on malformed scenarios
        if not self.seeds:
            raise ValueError("GridSpec.seeds must be non-empty")


def sim_for_config(sim: SimConfig, spec: GridSpec, ci: int, si: int,
                   pi: int, *, gi=None) -> Tuple[SimConfig, object]:
    """The per-config SimConfig + sigma dist a sequential reference run
    (``run_simulation_scan``) needs to reproduce grid cell (ci, si, pi) —
    or (ci, gi, si, pi) on a population grid (``gi`` indexes
    ``spec.populations``)."""
    cname, cparams = spec.channel_entries()[ci]
    pname, pparams = spec.policy_entries()[pi]
    pop = spec.population_entries()[gi] if gi is not None else None
    one = dataclasses.replace(sim, channel=cname, channel_params=cparams,
                              policy=pname, policy_params=pparams,
                              population=pop)
    return one, spec.sigma_dists[si]


def make_grid_runner(ds: FederatedDataset, sim: SimConfig,
                     scfg: SchedulerConfig, ch: ChannelConfig,
                     spec: GridSpec, *, devices=None):
    """Compile the grid into one ``jit(shard_map(...))`` call.

    Returns ``(runner, n_devices)``. ``runner(params, sigma_ids, keys)``
    takes per-cell config arrays — ``sigma_ids`` a tuple (one (C_cell,)
    int32 array per (channel, policy) cell, C_cell a multiple of
    ``n_devices``) and ``keys`` the matching (C_cell, 2) PRNG keys — and
    returns a tuple of per-cell ``(comm_time, test_acc, power_cum,
    n_selected)`` tuples, each leaf (C_cell, E). Use :func:`run_grid`
    unless you need to warm/reuse the compiled runner (benchmarks do).
    """
    spec.validate()
    if sim.participant_shards or sim.client_shards:
        raise ValueError(
            "the grid shards the CONFIG axis across the mesh; nesting the "
            "participant- or client-sharded round inside it is not "
            "supported — use sim.participant_shards / sim.client_shards "
            "with run_simulation, or the grid with both at 0")
    if sim.population is not None:
        raise ValueError(
            "the grid owns the population axis: leave sim.population unset "
            "and declare scenarios via GridSpec.populations")
    n = scfg.n_clients
    devices = list(devices if devices is not None else jax.devices())
    mesh = Mesh(np.array(devices), ("grid",))

    sigma_table = jnp.stack([resolve_sigmas(d, n) for d in spec.sigma_dists])
    # every cell runs the stitched decision, which the fused kernel is
    # bitwise-equal to; the solver name is still checked
    uses_fused(sim.solver, "proposed")
    round_core = make_round_core(ds, sim, scfg)
    eval_fn = make_eval_fn(ds, sim)
    co_host = decision_coeffs(scfg, ch)
    pops = spec.population_entries()
    if pops:
        from repro.fl.population import (init_active_mask,
                                         make_population_core,
                                         population_config)
        pop_bound = [(population_config(p),
                      make_population_core(
                          ds, sim, scfg, population_config(p)))
                     for p in pops]

    def make_cell(ci, pi, gi=None):
        """One (channel[, population], policy) cell: statically-bound
        config program."""
        cname, cparams = spec.channel_entries()[ci]
        pname, pparams = spec.policy_entries()[pi]
        init_fn, step_fn = CHANNEL_MODELS[cname]
        ckw = dict(cparams)
        if gi is not None:
            pcfg, pop_core = pop_bound[gi]

        def one_config(params, sid, key, co):
            # the policy binds to the RUNTIME coefficient bundle (operand
            # contract, repro/fl/decision.py) — same as run_simulation_scan
            policy_step = make_policy(pname, scfg, ch, m_avg=sim.uniform_m,
                                      coeffs=co.solve, **dict(pparams))
            sig = sigma_table[sid]
            ch_state = init_fn(jax.random.fold_in(key, CHANNEL_INIT_TAG),
                               sig, ch, **ckw)
            if gi is not None:
                # the (ch_state, active) carry of the population engine —
                # the same init as engine.init_channel_carry
                ch_state = (ch_state, init_active_mask(key, n, pcfg))
            pol_state = init_policy_state(pname, n)

            def channel_step(k, st):
                return step_fn(k, st, sig, ch, **ckw)

            core = round_core if gi is None else pop_core

            def sim_round(p, pst, cst, k):
                return core(channel_step, policy_step, co.acct, p,
                            pst, cst, k)

            # the same traced trajectory program as run_simulation_scan —
            # sharing the structure end to end is what makes grid cells
            # bitwise-reproducible by per-config runs
            return run_config_chunks(sim_round, eval_fn, sim.rounds,
                                     sim.eval_every, params, pol_state,
                                     ch_state, key)

        return one_config

    if pops:
        cell_fns = [make_cell(ci, pi, gi) for ci, gi, pi in spec.cells()]
    else:
        cell_fns = [make_cell(ci, pi) for ci, pi in spec.cells()]

    def shard_fn(params, sigma_ids, keys, co):
        # one sequential lax.map per cell: a config executes exactly its
        # own channel/policy code — no lax.switch, no masked branches
        return tuple(
            jax.lax.map(lambda cfg, f=f: f(params, *cfg, co), (sids, ks))
            for f, sids, ks in zip(cell_fns, sigma_ids, keys))

    sharded = shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(), P("grid"), P("grid"),
                  jax.tree.map(lambda _: P(), co_host)),
        out_specs=P("grid"))
    jitted = jax.jit(sharded)

    def runner(params, sigma_ids, keys):
        return jitted(params, sigma_ids, keys, co_host)

    return runner, len(devices)


def pad_to_multiple(arr: np.ndarray, multiple: int) -> np.ndarray:
    """Pad axis 0 up to a multiple by repeating the last row."""
    c = arr.shape[0]
    pad = (-c) % multiple
    if pad == 0:
        return arr
    return np.concatenate([arr, np.repeat(arr[-1:], pad, axis=0)], axis=0)


def grid_cell_inputs(key, spec: GridSpec, n_devices: int):
    """Per-cell (sigma_ids, keys) config arrays, padded to the device count.

    Within a cell, configs run in C-order over (sigma_dist, seed); the
    per-config key is ``fold_in(key, seed)``, shared across cells so equal
    seeds give the paired comparison the paper plots.
    """
    n_sig, n_seed = len(spec.sigma_dists), len(spec.seeds)
    sids = np.repeat(np.arange(n_sig, dtype=np.int32), n_seed)
    keys = np.stack([np.asarray(jax.random.fold_in(key, s))
                     for s in spec.seeds] * n_sig)
    sids = pad_to_multiple(sids, n_devices)
    keys = pad_to_multiple(keys, n_devices)
    n_cells = len(spec.cells())
    return tuple([sids] * n_cells), tuple([keys] * n_cells)


def run_grid(key, params, ds: FederatedDataset, sim: SimConfig,
             scfg: SchedulerConfig, ch: ChannelConfig, spec: GridSpec, *,
             devices=None) -> Dict[str, np.ndarray]:
    """Run the whole scenario grid in one shard_map-compiled call.

    Each config's key is ``fold_in(key, seed)`` — seeds shared across
    (channel, sigma, policy) cells give the paired comparison the paper
    plots. History layout matches :func:`run_simulation_scan` exactly:
    per config, ``comm_time`` / ``test_acc`` / ``avg_power`` /
    ``n_selected`` at each eval round, arranged as
    (channels, sigma_dists, policies, seeds, eval_points) — with a
    population axis after channels when ``spec.populations`` is set:
    (channels, populations, sigma_dists, policies, seeds, eval_points),
    plus a ``"populations"`` key listing the scenario dicts.

    Baseline policies need ``sim.uniform_m > 0`` (the matched average
    participation M — use ``repro.fl.simulation.match_uniform_m``). One M
    is shared by every cell: match it under the channel AND sigma mix you
    care about (``match_uniform_m(..., channel=...)``), and keep axes whose
    gain distribution shifts the match (rician/lognormal channels,
    homogeneous-vs-heterogeneous sigma mixes) in separate grids.
    Gauss-Markov shares Rayleigh's stationary gain law, so a
    Rayleigh-matched M transfers exactly across that channel axis.
    """
    spec.validate()
    needs_m = any(POLICIES[name][2] for name, _ in spec.policy_entries())
    if needs_m and not sim.uniform_m > 0.0:
        raise ValueError(
            "grid includes baseline policies: set sim.uniform_m > 0 "
            "(matched average participation; see match_uniform_m)")

    runner, n_dev = make_grid_runner(ds, sim, scfg, ch, spec,
                                     devices=devices)
    sigma_ids, keys = grid_cell_inputs(key, spec, n_dev)
    cell_outs = runner(params, sigma_ids, keys)

    n_ch, n_sig, n_pol, n_seed = spec.shape
    has_pop = bool(spec.populations)
    n_pop = len(spec.populations) if has_pop else 1
    ev = np.asarray(eval_rounds(sim.rounds, sim.eval_every))
    e = len(ev)
    c_cell = n_sig * n_seed
    # assemble (channels[, populations], sigma_dists, policies, seeds, E)
    # from the per-cell outputs, dropping padding; the population axis only
    # exists when GridSpec.populations is set
    shape = (n_ch, n_pop, n_sig, n_pol, n_seed, e)
    outs = {k: np.zeros(shape, np.float64)
            for k in ("comm_time", "test_acc", "power_cum")}
    outs["n_selected"] = np.zeros(shape, np.int64)
    for cell_key, cell in zip(spec.cells(), cell_outs):
        (ci, gi, pi) = cell_key if has_pop else (cell_key[0], 0,
                                                 cell_key[1])
        comm, acc, pcum, nsel = [np.asarray(x)[:c_cell] for x in cell]
        outs["comm_time"][ci, gi, :, pi] = comm.reshape(n_sig, n_seed, e)
        outs["test_acc"][ci, gi, :, pi] = acc.reshape(n_sig, n_seed, e)
        outs["power_cum"][ci, gi, :, pi] = pcum.reshape(n_sig, n_seed, e)
        outs["n_selected"][ci, gi, :, pi] = nsel.reshape(n_sig, n_seed, e)
    if not has_pop:
        outs = {k: v[:, 0] for k, v in outs.items()}

    # host-side float64 math mirrors run_simulation_scan's history exactly
    avg_power = outs.pop("power_cum") / (ev + 1) / ds.n_clients
    result = {
        "round": ev,
        "comm_time": outs["comm_time"],
        "test_acc": outs["test_acc"],
        "avg_power": avg_power,
        "n_selected": outs["n_selected"],
        "channels": [name for name, _ in spec.channel_entries()],
        "sigma_dists": [d if isinstance(d, str) else "custom"
                        for d in spec.sigma_dists],
        "policies": [name for name, _ in spec.policy_entries()],
        "seeds": np.asarray(spec.seeds),
        "n_devices": n_dev,
    }
    if has_pop:
        result["populations"] = [dict(p) for p in
                                 spec.population_entries()]
    return result
