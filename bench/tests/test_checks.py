"""Each comparison that decides ``correct`` passes on agreeing outputs and
fails when one layer's output is perturbed."""

import importlib.util
import os

import numpy as np
import pytest

import checks

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs")


def _load(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(CONFIGS, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _limits(name):
    import json
    with open(os.path.join(CONFIGS, f"{name}.limits.json")) as f:
        return json.load(f)


# ------------------------------------------------------------------ engine
def _engine_outputs(rng):
    params0 = {k: rng.normal(size=s) for k, s in
               (("w", (5, 5, 3, 4)), ("b", (4,)), ("f", (16, 10)))}
    first = {k: 0.9 * v + 0.01 * rng.normal(size=v.shape)
             for k, v in params0.items()}
    params = {k: v + 0.01 * rng.normal(size=v.shape)
              for k, v in first.items()}
    z_rounds = [np.abs(rng.normal(size=20)) * 10 for _ in range(3)]
    tie = np.zeros(20, bool)
    tie[5] = True
    follow = dict(z_kept=[z.copy() for z in z_rounds],
                  z_other=[z + 0.5 for z in z_rounds], tie=[tie] * 3,
                  t_comm=[(1.0, 1.0, 1.02)] * 3, power=[(3.9, 4.0, 4.0)] * 3)
    ref = dict(params=params, params_first=first, first_scale=0.9,
               z_rounds=z_rounds, t_comm_rounds=[1.0] * 3,
               power_rounds=[4.0] * 3, acc=0.31,
               grad_norms={"w": 1.0, "b": 0.2, "f": 0.5}, follow=follow,
               end=dict(key=np.array([7, 11], np.uint32),
                        z=np.abs(rng.normal(size=20)) * 10, power=830.0))
    return params0, ref


def _all_gaps(prog, ref, params0):
    return {**checks.engine_gaps(prog, ref, params0),
            **checks.engine_decision_gaps(prog, ref["follow"]),
            **checks.engine_end_gaps(prog["end"], ref["end"])}


def _copy(d):
    return {k: (_copy(v) if isinstance(v, dict) else np.copy(v)
                if isinstance(v, np.ndarray) else
                [np.copy(x) for x in v] if isinstance(v, list) else v)
            for k, v in d.items()}


ENGINE_PERTURB = {
    "decision queues": lambda p: p["z_rounds"][1].__setitem__(
        3, p["z_rounds"][1][3] + 1.0),
    "accounting time": lambda p: p["t_comm_rounds"].__setitem__(
        0, p["t_comm_rounds"][0] * 1.05),
    "accounting power": lambda p: p["power_rounds"].__setitem__(
        2, p["power_rounds"][2] * 1.01),
    "local training": lambda p: p["params"].__setitem__(
        "w", p["params"]["w"] * 1.2),
    "local training, every leaf": lambda p: p.update(params={
        k: v * 1.02 for k, v in p["params"].items()}),
    "first update": lambda p: p.update(params_first={
        k: v + 0.005 for k, v in p["params_first"].items()}),
    "frozen state": lambda p: None,
    "window rounds": lambda p: p["end"].update(
        key=np.array([7, 12], np.uint32)),
}


def test_engine_gaps_pass_on_agreement():
    params0, ref = _engine_outputs(np.random.default_rng(0))
    gaps = _all_gaps(_copy(ref), ref, params0)
    limits = _limits("cifar10_vi_a")
    assert all(gaps[k] <= limits[k] for k in limits), gaps


def test_engine_tie_client_may_keep_either_candidate():
    """On a tie client either candidate's queue and sums agree; the other
    candidate's queue elsewhere does not."""
    params0, ref = _engine_outputs(np.random.default_rng(0))
    prog = _copy(ref)
    prog["z_rounds"][1][5] = ref["follow"]["z_other"][1][5]
    prog["power_rounds"][1] = 3.9
    gaps = _all_gaps(prog, ref, params0)
    limits = _limits("cifar10_vi_a")
    assert all(gaps[k] <= limits[k] for k in limits), gaps
    prog["z_rounds"][1][4] = ref["follow"]["z_other"][1][4]
    assert checks.engine_decision_gaps(prog, ref["follow"])["z_gap"] > \
        limits["z_gap"]


def test_follow_decisions_of_own_run_agree():
    """The reference's own run, followed at its own queues, has no gap."""
    from conftest import TINY_ENGINE
    import jax
    ref_mod = _load("cifar10_vi_a_ref")
    key = jax.random.PRNGKey(3)
    k, z = key, np.zeros(TINY_ENGINE["n_clients"])
    own = {"z_rounds": [], "t_comm_rounds": [], "power_rounds": []}
    for _ in range(4):
        k, _, _, _, t_comm, power, z, _ = ref_mod.decide(k, z, TINY_ENGINE)
        own["z_rounds"].append(np.asarray(z))
        own["t_comm_rounds"].append(float(t_comm))
        own["power_rounds"].append(float(power))
    follow = ref_mod.follow_decisions(key, TINY_ENGINE, own["z_rounds"])
    gaps = checks.engine_decision_gaps(own, follow)
    assert gaps["z_gap"] == 0 and gaps["t_comm_gap"] == 0, gaps
    assert gaps["power_gap"] == 0, gaps


@pytest.mark.parametrize("layer", sorted(ENGINE_PERTURB))
def test_engine_gaps_fail_on_perturbed_layer(layer):
    params0, ref = _engine_outputs(np.random.default_rng(1))
    prog = _copy(ref)
    if layer == "frozen state":
        prog["params"] = prog["params_first"] = dict(params0)
    else:
        ENGINE_PERTURB[layer](prog)
    gaps = _all_gaps(prog, ref, params0)
    limits = _limits("cifar10_vi_a")
    assert any(gaps[k] > limits[k] for k in limits), gaps


# ----------------------------------------------------------------- service
def _generator():
    spec = importlib.util.spec_from_file_location(
        "generator", os.path.join(os.path.dirname(CONFIGS), "generator.py"))
    gen = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gen)
    return gen


CFG = {"bandwidth_hz": 22e6, "noise_power": 1.0, "p_max": 100.0,
       "p_bar": 1.0, "q_floor": 1e-5, "guarantee_one": True,
       "max_spectral_eff": 10.0, "min_spectral_eff": 0.25}


def _draws(rng, n):
    gen = _generator()
    sig = gen.sigmas([n // 10, 4 * n // 10, n - n // 10 - 4 * n // 10],
                     [0.2, 0.75, 1.2])
    return gen, [gen.TenantDraw(f"t{i}", n, "proposed", 1000.0, lam,
                                float(rng.uniform(1e7, 2e7)), 100.0, sig)
                 for i, lam in enumerate((10.0, 100.0, 30.0))]


def _service_case(rng, n=24, steps=30):
    ref_mod = _load("tenants_paper_grid_ref")
    gen, draws = _draws(rng, n)
    g, raws = gen.make_payloads(CFG, draws, steps, rng)
    seqs = [list(zip(g[i], raws[i])) for i in range(3)]
    # the "program": the reference in float32, as a float32 program would
    prog32 = ref_mod.replay(CFG, draws, seqs, dtype=np.float32)
    prog = {k: prog32[k].astype(np.float32) for k in
            ("q", "p", "t_comm", "power")}
    prog.update(sel=prog32["sel"].copy(), served=prog32["steps"].copy(),
                z=prog32["z"].copy())
    ref = ref_mod.follow(CFG, draws, seqs, prog["q"], prog["p"],
                         prog["served"])
    return prog, ref


def test_follow_of_own_decisions_is_replay():
    """Decided at the queues its own decisions lead to, the reference
    makes its own run again, and so in blocks."""
    rng = np.random.default_rng(5)
    ref_mod = _load("tenants_paper_grid_ref")
    gen, draws = _draws(rng, 20)
    g, raws = gen.make_payloads(CFG, draws, 12, rng)
    seqs = [list(zip(g[i], raws[i]))[:12 - i] for i in range(3)]
    whole = ref_mod.replay(CFG, draws, seqs)
    first = ref_mod.follow(CFG, draws, [s[:5] for s in seqs],
                           whole["q"][:, :5], whole["p"][:, :5],
                           whole["steps"][:, :5])
    rest = ref_mod.follow(CFG, draws, [s[5:] for s in seqs],
                          whole["q"][:, 5:], whole["p"][:, 5:],
                          whole["steps"][:, 5:], z0=first["z"])
    for k in ("sel", "q", "p", "z_before"):
        np.testing.assert_allclose(
            np.concatenate([first[k], rest[k]], axis=1)[whole["steps"]],
            whole[k][whole["steps"]], rtol=1e-12, atol=0)
    np.testing.assert_allclose(rest["z"], whole["z"], rtol=1e-12, atol=0)


def test_follow_judges_each_decision_at_its_own_state():
    """A program that took the other, equally good, candidate once (a
    Pmax tie) runs on from other queues; followed, its later decisions
    agree with the reference at those queues, where the reference's own
    run compares them at queues of its own."""
    rng = np.random.default_rng(6)
    ref_mod = _load("tenants_paper_grid_ref")
    gen, draws = _draws(rng, 24)
    g, raws = gen.make_payloads(CFG, draws, 40, rng)
    seqs = [list(zip(g[i], raws[i])) for i in range(3)]
    own = ref_mod.replay(CFG, draws, seqs)
    # the program's queues after a kick at step 3 of tenant 0, lane 0
    z0 = own["z_before"][:, 3].copy()
    z0[0, 0] += 5.0
    rest = [s[3:] for s in seqs]
    prog64 = ref_mod.replay(CFG, draws, rest, z0=z0)
    prog = {k: prog64[k].astype(np.float32) for k in
            ("q", "p", "t_comm", "power")}
    prog.update(sel=prog64["sel"], served=prog64["steps"],
                z=prog64["z"].astype(np.float32))
    limits = _limits("tenants_paper_grid")
    followed = ref_mod.follow(CFG, draws, rest, prog["q"], prog["p"],
                              prog["served"], z0=z0)
    gaps = checks.service_gaps(prog, followed, followed["objective"])
    assert all(gaps[k] <= limits[k] for k in limits), gaps
    own_rest = ref_mod.replay(CFG, draws, rest,
                              z0=own["z_before"][:, 3].copy())
    drifted = checks.service_gaps(prog, own_rest, own_rest["objective"])
    assert drifted["p_gap"] > limits["p_gap"], drifted


def test_replay_in_blocks_matches_one_replay():
    rng = np.random.default_rng(4)
    ref_mod = _load("tenants_paper_grid_ref")
    gen, draws = _draws(rng, 20)
    g, raws = gen.make_payloads(CFG, draws, 12, rng)
    seqs = [list(zip(g[i], raws[i]))[:12 - i] for i in range(3)]
    whole = ref_mod.replay(CFG, draws, seqs)
    first = ref_mod.replay(CFG, draws, [s[:5] for s in seqs])
    rest = ref_mod.replay(CFG, draws, [s[5:] for s in seqs],
                          z0=first["z_dtype"])
    np.testing.assert_array_equal(rest["z"], whole["z"])
    np.testing.assert_array_equal(rest["q"][:, :4], whole["q"][:, 5:9])
    assert rest["steps"].sum() + first["steps"].sum() == whole["steps"].sum()


SERVICE_PERTURB = {
    "missing decision": lambda p: p["served"].__setitem__((1, 4), False),
    "selection": lambda p: p["sel"].__setitem__(
        (0, 2, 0), ~p["sel"][0, 2, 0]),
    "probability": lambda p: p["q"].__setitem__((2, 7, 3),
                                                p["q"][2, 7, 3] * 1.01),
    "power": lambda p: p["p"].__setitem__((2, 7, 3), p["p"][2, 7, 3] + 1.0),
    "accounting": lambda p: p["t_comm"].__setitem__(
        (0, 5), p["t_comm"][0, 5] * 1.01),
    "queues": lambda p: p["z"].__setitem__((1, 0), p["z"][1, 0] + 1.0),
}


@pytest.mark.parametrize("n", [100, 3597])
def test_service_gaps_pass_on_agreement(n):
    prog, ref = _service_case(np.random.default_rng(2), n=n, steps=8)
    gaps = checks.service_gaps(prog, ref, ref["objective"])
    limits = _limits("tenants_paper_grid")
    assert all(gaps[k] <= limits[k] for k in limits), gaps


@pytest.mark.parametrize("layer", sorted(SERVICE_PERTURB))
def test_service_gaps_fail_on_perturbed_layer(layer):
    prog, ref = _service_case(np.random.default_rng(3))
    SERVICE_PERTURB[layer](prog)
    gaps = checks.service_gaps(prog, ref, ref["objective"])
    limits = _limits("tenants_paper_grid")
    assert any(gaps[k] > limits[k] for k in limits), (layer, gaps)
