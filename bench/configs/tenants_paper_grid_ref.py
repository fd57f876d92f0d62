"""Plain reference of the multi-tenant scheduler for ``tenants_paper_grid``.

A tenant's decisions depend only on its own configuration, its requests
and its queues, which start empty. The reference decides with
``theorem2_ref`` (Theorem 2, selection, Eq. 9, Eq. 8), tenants of one
width side by side, a block of requests at a time, in two ways:

* ``follow``: each request at the queues that the decisions served before
  it lead to by Eq. 9, from empty queues; given the program's decisions,
  this judges every decision at the state the program decided it in;
* ``replay``: the reference's own run from empty queues, every request
  at the queues its own decisions lead to (the control's path).
"""

from __future__ import annotations

import importlib.util
import os

import numpy as np

_spec = importlib.util.spec_from_file_location(
    "theorem2_ref", os.path.join(os.path.dirname(__file__), "theorem2_ref.py"))
t2 = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(t2)


def columns(cfg: dict, draws, ndim: int = 2) -> "t2.Tenant":
    """The tenants' scalars as columns shaped (T, 1, ...) for ``ndim``-d
    lane arrays."""
    shape = (len(draws),) + (1,) * (ndim - 1)

    def col(f):
        return np.array([f(d) for d in draws], np.float64).reshape(shape)

    return t2.Tenant(
        n=col(lambda d: d.n), v=col(lambda d: d.V), lam=col(lambda d: d.lam),
        ell=col(lambda d: d.ell), bw=col(lambda d: cfg["bandwidth_hz"]),
        n0=col(lambda d: cfg["noise_power"]),
        p_max=col(lambda d: d.p_max), p_bar=col(lambda d: cfg["p_bar"]),
        q_floor=col(lambda d: cfg["q_floor"]))


def requests(draws, seqs):
    """The request sequences ``seqs`` (per tenant a list of (gains,
    uniforms)) as (T, S, N) gains ``g`` and uniforms ``u`` and (T, S)
    ``steps``, whether request s was made."""
    t, n = len(draws), draws[0].n
    s_max = max(len(s) for s in seqs)
    steps = np.zeros((t, s_max), bool)
    g = np.ones((t, s_max, n))
    u = np.full((t, s_max, n), np.nan)
    for i, seq in enumerate(seqs):
        steps[i, :len(seq)] = True
        for s, (gains, uni) in enumerate(seq):
            g[i, s] = gains
            u[i, s] = uni
    return g, u, steps


def _finish(out: dict, cfg: dict, draws, g) -> dict:
    t3 = columns(cfg, draws, ndim=3)
    out.update(
        p_max=t3.p_max, p_bar=cfg["p_bar"],
        account=lambda sel, q, p: t2.account(
            sel, np.asarray(q, np.float64), np.asarray(p, np.float64), g, t3),
        objective=lambda q, p, gg, zz: t2.objective(
            np.asarray(q, np.float64), np.asarray(p, np.float64), gg, zz, t3))
    return out


def follow(cfg: dict, draws, seqs, q, p, served, z0=None) -> dict:
    """Every decision of tenants ``draws`` (one width) over their request
    sequences ``seqs``, each at the queues that the served decisions
    before it lead to: ``z_before`` of request s is Eq. 9 applied to the
    given (T, S, N) ``q`` and ``p`` of the requests before it that
    ``served`` (T, S) marks, from queues ``z0`` (empty where None). The
    requests are independent given those queues, so all are decided at
    once.

    Returns what ``replay`` returns, with ``z`` (and ``z_dtype``) the
    queues after the last served request."""
    t, n = len(draws), draws[0].n
    g, u, steps = requests(draws, seqs)
    z = np.zeros((t, n)) if z0 is None else np.asarray(z0, np.float64)
    z_before = np.zeros(g.shape)
    q = np.asarray(q, np.float64)
    p = np.asarray(p, np.float64)
    for s in range(g.shape[1]):
        z_before[:, s] = z
        z = np.where(served[:, s, None],
                     t2.queue_update(z, q[:, s], p[:, s], cfg["p_bar"]), z)
    t3 = columns(cfg, draws, ndim=3)
    with np.errstate(all="ignore"):   # lanes of requests never made
        sel, q_r, p_r, t_comm, power, _ = t2.proposed(
            u, g, z_before, t3, cfg["guarantee_one"])
    out = dict(sel=sel, q=q_r, p=p_r, t_comm=t_comm, power=power, g=g, u=u,
               steps=steps, z_before=z_before, z=z, z_dtype=z)
    return _finish(out, cfg, draws, g)


def replay(cfg: dict, draws, seqs, z0=None, dtype=np.float64) -> dict:
    """Every decision of tenants ``draws`` (one width) over their request
    sequences ``seqs`` (per tenant a list of (gains, uniforms)), from
    queues ``z0`` (empty where None), each at the queues of the
    reference's own decisions before it.

    Returns (T, S, N) ``sel``, ``q``, ``p``, ``g``, ``u`` and
    ``z_before``, (T, S) ``t_comm``, ``power`` and ``steps`` (whether
    request s was made), the final (T, N) queues ``z`` (in ``dtype`` as
    ``z_dtype``), and ``p_max`` (T, 1, 1), ``p_bar``, ``account`` and
    ``objective`` over these tenants."""
    t, n = len(draws), draws[0].n
    g, u, steps = requests(draws, seqs)
    s_max = g.shape[1]
    tc = columns(cfg, draws)
    z = np.zeros((t, n), dtype) if z0 is None else np.asarray(z0, dtype)
    out = {k: np.zeros((t, s_max, n)) for k in ("q", "p", "z_before")}
    out["sel"] = np.zeros((t, s_max, n), bool)
    out["t_comm"] = np.zeros((t, s_max))
    out["power"] = np.zeros((t, s_max))
    with np.errstate(all="ignore"):
        for s in range(s_max):
            out["z_before"][:, s] = z
            sel, q, p, t_comm, power, z_new = t2.proposed(
                u[:, s], g[:, s], z, tc, cfg["guarantee_one"], dtype)
            z = np.where(steps[:, s, None], z_new, z)
            out["sel"][:, s] = sel
            for k, v in (("q", q), ("p", p), ("t_comm", t_comm),
                         ("power", power)):
                out[k][:, s] = np.asarray(v, np.float64)
    out.update(g=g, u=u, steps=steps, z=np.asarray(z, np.float64),
               z_dtype=z)
    return _finish(out, cfg, draws, g)
