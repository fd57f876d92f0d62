"""Plain reference of the Section VI-A scheduler over a cross-device fleet.

Each round (Algorithm 2 alone, no training) draws every client's gain and
selection uniform from the run key exactly as the Section VI-A rounds do
(``cifar10_vi_a_ref``: the key chain, the draws and the gains), then
decides by Theorem 2, selects, updates the Eq. 9 queues and sums Eq. 8
(``theorem2_ref``), in float64, lane by lane.

The program returns, for each round of a chunk, its Eq. 8 sums, its
selection count, the selected clients past its id slots, and the
selected client ids; and its queues only at the chunk's end. So the
reference cannot decide each round at the program's own queues, as the
engine cell's does; it follows each lane from empty queues instead. A
client's decision depends only on its own gain, uniform and queue (the
guarantee-one fallback aside, which a fleet never reaches), so the lanes
are independent chains:

* where a client's two Theorem-2 candidates (interior and Pmax) differ
  and lie within ``TIE`` of each other in Eq. (15)'s objective, either is
  optimal to round-off and a float32 program may keep either: the lane
  goes on along both branches;
* each round, a lane's branches whose selection disagrees with the
  program's are dropped (where one agrees), so later rounds are judged
  against the branch nearer the program, and its queues at the end
  against the nearest branch left;
* a selection uniform within ``Q_BAND`` of q (relative) may fall either
  way in float32, so a lane whose draw lies there agrees with both;
* the Eq. 8 sums are judged against the (least, most) range over the
  branches, the comm time over the program's own selection.
"""

from __future__ import annotations

import importlib.util
import os

import numpy as np


def _load(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(os.path.dirname(__file__), f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


vi_a = _load("cifar10_vi_a_ref")
t2 = vi_a.t2

F64 = np.float64
TIE = vi_a.TIE
# A float32 q lies within about 1e-4 of the float64 one, relatively (the
# service cell's widest q gap, PERF.md section 6); a selection uniform
# within ten times that of q may fall either way.
Q_BAND = 1e-3


sigmas = vi_a.sigmas


def rows_of(sel: np.ndarray, cap: int) -> tuple:
    """A (rounds, n) selection as the program's rows: -> (ids (rounds,
    cap) ascending and zero-filled, n_sel, overflow)."""
    n_sel = sel.sum(axis=1).astype(np.int64)
    ids = np.zeros((sel.shape[0], cap), np.int64)
    for r, s in enumerate(sel):
        first = np.flatnonzero(s)[:cap]
        ids[r, :first.size] = first
    return ids, n_sel, n_sel - np.minimum(n_sel, cap)


def own_chunk(key, cfg, rounds: int, control: bool = False) -> dict:
    """The reference's own chunk from empty queues, as the program reports
    one (float64, or bfloat16 throughout for the control): per round
    ``ids``, ``n_sel``, ``overflow``, ``t_comm``, ``power``, and the
    queues ``z`` after it."""
    dtype = __import__("ml_dtypes").bfloat16 if control else F64
    z = np.zeros(cfg["n_clients"], F64)
    sels, t_comm, power = [], [], []
    for _ in range(rounds):
        key, sel, _, _, t, p, z, _ = vi_a.decide(key, z, cfg, dtype)
        sels.append(sel)
        t_comm.append(float(t))
        power.append(float(p))
    ids, n_sel, overflow = rows_of(np.array(sels), cfg["sel_cap"])
    return dict(ids=ids, n_sel=n_sel, overflow=overflow,
                t_comm=np.array(t_comm), power=np.array(power),
                z=np.asarray(z, F64))


def key_after(key, rounds: int) -> np.ndarray:
    """The run key after ``rounds`` rounds (each splits it once)."""
    import jax

    after = jax.jit(lambda k, r: jax.lax.fori_loop(
        0, r, lambda _, k: jax.random.split(k)[0], k))
    return np.asarray(after(key, rounds))


def _program_selection(ids, n_sel, overflow, n: int, cap: int):
    """One round's program selection from its row: -> (sel (n,), known
    (n,) lanes whose selection the row fixes, count of malformed slots:
    ids out of order or range, a fill that is not zero, an overflow that
    is not n_sel past the cap)."""
    k = int(min(n_sel, cap))
    got = np.asarray(ids[:k], np.int64)
    bad = int(np.sum(np.asarray(ids[k:]) != 0))
    bad += int(np.sum((got < 0) | (got >= n)))
    bad += int(np.sum(np.diff(got) <= 0))
    bad += int(overflow != n_sel - k)
    sel = np.zeros(n, bool)
    sel[got[(got >= 0) & (got < n)]] = True
    known = np.ones(n, bool)
    if n_sel > cap and k:
        known[got[-1] + 1:] = False   # past the last slot: not reported
    return sel, known, bad


def _spread(lane, x, n):
    """Per lane, the least and the most of ``x`` over its branches."""
    lo = np.full(n, np.inf)
    hi = np.full(n, -np.inf)
    np.minimum.at(lo, lane, x)
    np.maximum.at(hi, lane, x)
    return lo, hi


def _outside(x: float, lo: float, hi: float) -> float:
    """How far x lies outside [lo, hi], over the range's middle."""
    off = max(lo - x, x - hi, 0.0)
    return 0.0 if off == 0.0 else off / max(abs(lo + hi) / 2, 1e-30)


def judge(key, cfg, prog: dict) -> dict:
    """One chunk of the program against the lanes followed from empty
    queues (see the module docstring).

    ``prog`` holds per round ``ids`` (rounds, cap), ``n_sel``,
    ``overflow``, ``t_comm``, ``power``, and the queues ``z`` after the
    chunk. Returns:

    * ``id_mismatch``: lane-rounds whose program selection agrees with
      none of the lane's branches (a draw within ``Q_BAND`` of q agrees
      either way), plus malformed slots of the rows;
    * ``t_comm_gap``, ``power_gap``: how far each round's Eq. 8 sum lies
      outside the range over the branches, over the range's middle;
    * ``z_gap``: the end queues against the nearest branch, over the
      largest queue (at least Pbar);
    * readings: ``tie_lanes`` (lane-rounds with a tie) and
      ``band_draws`` (lane-rounds excused by ``Q_BAND``)."""
    n, cap = cfg["n_clients"], cfg["sel_cap"]
    t = vi_a.tenant(cfg)
    lane = np.arange(n)
    z = np.zeros(n, F64)
    out = dict(id_mismatch=0, t_comm_gap=0.0, power_gap=0.0, tie_lanes=0,
               band_draws=0)
    for r in range(len(prog["n_sel"])):
        key, g, u, _ = vi_a.draws(key, cfg)
        g, u = g[0], u[0]
        ge = g[lane][None]
        (q_i, p_i, f_i), (q_b, p_b, f_b) = t2.candidates(ge, z[None], t)
        q_i, p_i, f_i, q_b, p_b, f_b = (a[0] for a in (q_i, p_i, f_i, q_b,
                                                        p_b, f_b))
        use_int = np.isfinite(f_i) & (f_i <= f_b)
        tie = (np.isfinite(f_i) & (np.abs(f_i - f_b) <= TIE * np.abs(f_b))
               & ((q_i != q_b) | (p_i != p_b)))
        out["tie_lanes"] += int(tie.sum())
        q_k, p_k = np.where(use_int, q_i, q_b), np.where(use_int, p_i, p_b)
        q_o, p_o = np.where(use_int, q_b, q_i), np.where(use_int, p_b, p_i)
        lane = np.concatenate([lane, lane[tie]])
        z = np.concatenate([z, z[tie]])
        q = np.concatenate([q_k, q_o[tie]])
        p = np.concatenate([p_k, p_o[tie]])
        sel_e = u[lane] < q
        if cfg["guarantee_one"] and not sel_e.any():
            sel_e[np.argmax(q)] = True
        sel_p, known, bad = _program_selection(
            prog["ids"][r], int(prog["n_sel"][r]), int(prog["overflow"][r]),
            n, cap)
        band = np.abs(u[lane] - q) <= Q_BAND * q
        agree = (sel_e == sel_p[lane]) | band
        lane_ok = np.bincount(lane, weights=agree, minlength=n) > 0
        out["id_mismatch"] += bad + int(np.sum(~lane_ok & known))
        out["band_draws"] += int(np.sum(band & (sel_e != sel_p[lane])))
        keep = agree | ~lane_ok[lane]
        # Eq. 8 over the program's selection, per branch
        tc, pq = t2.lane_account(sel_p[lane][None], q[None], p[None],
                                 g[lane][None], t)
        for name, x in (("t_comm", tc[0]), ("power", pq[0])):
            lo, hi = _spread(lane[keep], x[keep], n)
            gap = _outside(float(prog[name][r]), float(lo.sum()),
                           float(hi.sum()))
            out[f"{name}_gap"] = max(out[f"{name}_gap"], gap)
        z_new = t2.queue_update(z, q, p, t.p_bar[0, 0])
        lane, z_new = lane[keep], z_new[keep]
        order = np.lexsort((z_new, lane))
        lane, z = lane[order], z_new[order]
        fresh = np.concatenate([[True], (lane[1:] != lane[:-1])
                                | (z[1:] != z[:-1])])
        lane, z = lane[fresh], z[fresh]
    z_p = np.asarray(prog["z"], F64)
    near = np.full(n, np.inf)
    np.minimum.at(near, lane, np.abs(z_p[lane] - z))
    scale = max(float(z.max(initial=0.0)), float(cfg["p_bar"]))
    out["z_gap"] = float(near.max()) / scale
    return out
