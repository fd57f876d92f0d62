"""The comparisons that decide ``correct``: the program against the plain
reference, each as a few numbers that the limits files bound.

Every number is a gap at which 0 is perfect agreement; a run is correct
when each is at most its limit (``bench/configs/<config>.limits.json``).
"""

from __future__ import annotations

import numpy as np


def rel_gap(a, b, floor=0.0) -> float:
    """Largest |a - b| over max(|b|, floor), elementwise."""
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    if a.shape != b.shape:
        return float("inf")
    if a.size == 0:
        return 0.0
    den = np.maximum(np.abs(b), floor)
    with np.errstate(divide="ignore", invalid="ignore"):
        gap = np.where(a == b, 0.0, np.abs(a - b) / den)  # 0 == 0 agrees
    return float(np.max(gap))


def masked_gap(a, b, mask, floor=0.0) -> float:
    """:func:`rel_gap` over the entries where ``mask`` holds."""
    a, b = np.broadcast_arrays(np.asarray(a, np.float64),
                               np.asarray(b, np.float64))
    mask = np.broadcast_to(mask, a.shape)
    floor = np.broadcast_to(np.asarray(floor, np.float64), a.shape)
    return rel_gap(a[mask], b[mask], floor[mask])


def leaf_norm_gaps(prog_delta: dict, ref_delta: dict, ref_grad: dict,
                   grad_rule: float = 1e-3) -> dict:
    """Per leaf, the gap between the norms of the program's and the
    reference's parameter change, over the larger of that leaf's reference
    norm and the median leaf's. Leaves whose reference gradient is under
    ``grad_rule`` of the median leaf's move by round-off alone and are left
    out. -> {leaf: gap}, empty where the two sides' leaves differ."""
    names = sorted(ref_delta)
    if sorted(prog_delta) != names:
        return {}
    grads = np.array([ref_grad[k] for k in names], np.float64)
    keep = grads >= grad_rule * np.median(grads)
    pn = np.array([np.linalg.norm(np.asarray(prog_delta[k], np.float64))
                   for k in names])
    rn = np.array([np.linalg.norm(np.asarray(ref_delta[k], np.float64))
                   for k in names])
    den = np.maximum(rn, np.median(rn[keep]))
    gaps = np.abs(pn - rn) / den
    return {k: float(g) for k, g, kept in zip(names, gaps, keep) if kept}


def leaf_diff_gap(prog_delta: dict, ref_delta: dict) -> float:
    """The worst leaf's norm of the difference of the two changes, over the
    larger of that leaf's reference norm and the median leaf's."""
    names = sorted(ref_delta)
    if sorted(prog_delta) != names:
        return float("inf")
    rn = np.array([np.linalg.norm(ref_delta[k]) for k in names])
    dn = np.array([np.linalg.norm(np.asarray(prog_delta[k], np.float64)
                                  - ref_delta[k]) for k in names])
    return float(np.max(dn / np.maximum(rn, np.median(rn))))


def _worst_and_median(per_leaf: dict) -> tuple:
    v = np.array(list(per_leaf.values()))
    if not v.size:
        return float("inf"), float("inf")
    return float(np.max(v)), float(np.median(v))


def engine_gaps(prog: dict, ref: dict, params0: dict) -> dict:
    """The set-up's federated rounds, program against reference: the
    model.

    ``prog`` and ``ref`` hold the model after the first chunk
    (``params_first``) and after the set-up (``params``) and the test
    ``acc`` after the set-up; ``ref`` also holds its ``grad_norms`` and
    ``first_scale``.

    * ``first_update_gap``, ``first_update_median_gap``: the first
      chunk's update as the model gets it, x_1 - s x_0, with s the factor
      by which aggregation alone scales the model (the reference's), by
      its worst and its median leaf (``first_leaf.<leaf>`` each kept
      leaf's gap);
    * ``param_change_gap``, ``param_change_median_gap``: the change
      after the set-up, x - x_0, by its worst and its median leaf.
    """
    def delta(p, s=1.0):
        return {k: np.asarray(p[k], np.float64)
                - s * np.asarray(params0[k], np.float64) for k in p}

    s = ref["first_scale"]
    first = leaf_norm_gaps(delta(prog["params_first"], s),
                           delta(ref["params_first"], s), ref["grad_norms"])
    change = leaf_norm_gaps(delta(prog["params"]), delta(ref["params"]),
                            ref["grad_norms"])
    first_worst, first_median = _worst_and_median(first)
    change_worst, change_median = _worst_and_median(change)
    return {
        **{f"first_leaf.{k}": v for k, v in first.items()},
        "first_update_gap": first_worst,
        "first_update_median_gap": first_median,
        "param_change_gap": change_worst,
        "param_change_median_gap": change_median,
        "param_diff_gap": leaf_diff_gap(delta(prog["params"]),
                                        delta(ref["params"])),
        "acc_gap": abs(float(prog["acc"]) - float(ref["acc"])),
    }


def _outside(x: float, bounds) -> float:
    """How far x lies outside [least, most], over |kept|."""
    least, kept, most = bounds
    off = max(least - x, x - most, 0.0)
    return 0.0 if off == 0.0 else off / max(abs(kept), 1e-30)


def engine_decision_gaps(prog: dict, follow: dict) -> dict:
    """The set-up's decision layer, each round judged at the program's
    own queues before it (``follow``, the reference's
    ``follow_decisions`` over ``prog["z_rounds"]``).

    ``prog`` holds each round's queues after it (``z_rounds``) and its
    summed comm time and expected power (``t_comm_rounds``,
    ``power_rounds``).

    * ``z_gap``: the program's queues after each round against Eq. 9 of
      the reference's decision at the queues before it, on a tie client
      against the nearer of its two candidates, over the largest queue;
    * ``t_comm_gap``, ``power_gap``: how far each round's Eq. 8 sum lies
      outside the reference's over the choices on the tie clients, over
      the kept choice's sum.
    """
    z_scale = max(max(float(np.max(np.abs(z))) for z in follow["z_kept"]),
                  1e-30)
    z_gap = 0.0
    for z, z_k, z_o, tie in zip(prog["z_rounds"], follow["z_kept"],
                                follow["z_other"], follow["tie"]):
        z = np.asarray(z, np.float64)
        off = np.abs(z - z_k)
        off = np.where(tie, np.minimum(off, np.abs(z - z_o)), off)
        z_gap = max(z_gap, float(np.max(off)) / z_scale)
    return {
        "z_gap": z_gap,
        "t_comm_gap": max(_outside(x, b) for x, b in zip(
            prog["t_comm_rounds"], follow["t_comm"])),
        "power_gap": max(_outside(x, b) for x, b in zip(
            prog["power_rounds"], follow["power"])),
        "tie_clients": int(sum(np.sum(t) for t in follow["tie"])),
    }


def engine_end_gaps(prog: dict, ref: dict) -> dict:
    """The carry after the window, program against the reference's
    decision layer over the same rounds: the run key (``key_mismatch``,
    words that differ: every round splits it once, so it counts the rounds
    run), the queues ``z`` and the summed expected power ``power``. Neither
    depends on which clients a Bernoulli draw selected (Eq. 9 and Eq. 8's
    power use q and P), so a draw on its threshold moves neither."""
    z_scale = max(float(np.max(np.abs(ref["z"]))), 1e-30)
    key_p = np.asarray(prog["key"]).ravel()
    key_r = np.asarray(ref["key"]).ravel()
    return {
        "key_mismatch": (int(np.sum(key_p != key_r))
                         if key_p.shape == key_r.shape else key_r.size),
        "z_end_gap": rel_gap(prog["z"], ref["z"], floor=z_scale),
        "power_end_gap": rel_gap(prog["power"], ref["power"]),
    }


def service_gaps(prog: dict, ref: dict, objective) -> dict:
    """One group of tenants of one width, every decision of each.

    Both dicts hold (T, S, N) ``sel``, ``q``, ``p`` and (T, S) ``t_comm``,
    ``power`` over each tenant's S requests, and (T, N) final queues ``z``;
    ``prog`` holds ``served`` (T, S), whether each decision came back;
    ``ref`` holds ``u`` (T, S, N) selection uniforms,
    ``g`` gains and ``z_before`` queues before each decision (the queues
    the program's decisions before it lead to), ``p_max`` (T, 1, 1) and
    ``steps`` (T, S), whether each request was made; its ``z`` is Eq. 9
    applied to the program's decisions.
    ``objective(q, p, g, z)`` is Eq. (15) per lane for these tenants.

    * ``missing`` counts requests without a decision;
    * ``sel_mismatch`` counts lanes selected differently where the
      selection uniform does not lie between the two sides' q (where it
      does, the draw sits on the threshold and the q gap bounds the case);
    * ``q_gap`` is the largest relative gap of q;
    * ``p_gap`` the largest gap of P over Pmax, away from Pmax ties: where
      one side keeps P = Pmax and the other an interior optimum, f is flat
      in P and both candidates are optimal to round-off, so there
    * ``tie_objective_gap`` holds the two to Eq. (15)'s objective;
    * ``acct_gap`` holds each side's comm time and power to Eq. 8 of its
      own decision (the reference's accounting applied to the program's
      selection and powers);
    * ``z_gap`` is the final queues' largest gap over max(Z, Pbar): the
      program's state against Eq. 9 applied to its own decisions.
    """
    made = ref["steps"]
    served = prog["served"] & made
    out = {"missing": int(np.sum(made & ~prog["served"]))}
    m = served[..., None] & np.ones(ref["q"].shape, bool)
    sel_p, sel_r = prog["sel"], ref["sel"]
    u, q_r = ref["u"], ref["q"]
    between = ((u >= np.minimum(prog["q"], q_r))
               & (u <= np.maximum(prog["q"], q_r)))
    out["sel_mismatch"] = int(np.sum(m & (sel_p != sel_r) & ~between))
    out["q_gap"] = masked_gap(prog["q"], q_r, m)
    p_max = ref["p_max"]
    tie = m & ((prog["p"] == p_max.astype(np.float32))
               != (ref["p"] == p_max))
    plain = m & ~tie
    out["p_gap"] = masked_gap(prog["p"], ref["p"], plain, floor=p_max)
    with np.errstate(all="ignore"):   # lanes of requests never made
        f_p = objective(prog["q"], prog["p"], ref["g"], ref["z_before"])
        f_r = objective(q_r, ref["p"], ref["g"], ref["z_before"])
    out["tie_objective_gap"] = masked_gap(f_p, f_r, tie)
    out["pmax_ties"] = int(tie.sum())
    t_own, p_own = ref["account"](sel_p, prog["q"], prog["p"])
    out["acct_gap"] = max(masked_gap(prog["t_comm"], t_own, served),
                          masked_gap(prog["power"], p_own, served))
    out["z_gap"] = rel_gap(prog["z"], ref["z"],
                           floor=float(ref["p_bar"]))
    return out
