"""Plain reference of one scheduling decision, in NumPy, at a chosen dtype.

The paper's Algorithm 2 for one round of one FL deployment: the Theorem-2
solve of the per-client drift-plus-penalty problem (Eq. 15), Bernoulli
selection from the request's uniforms, the Eq. 9 virtual-queue update and
the Eq. 8 accounting (TDMA communication time, expected power). Written
from the equations, independently of the code under test; every array is
(tenants, lanes) and every operation is rounded to ``dtype`` (float64 for
the reference, bfloat16 for the control), so one code path serves both.

The interior candidate uses A = V lam ell |h|^2 ln 2 / (N0 B Z): one power
of ln 2, as d f / d P = 0 gives it (the paper prints two). The kept
candidate is the one of smaller objective, which is what Theorem 2's
Hessian test selects.
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np

LN2 = 0.6931471805599453
EPS = 1e-12
HALLEY_ITERS = 10


class Tenant(NamedTuple):
    """One deployment's scalars, each a (tenants, 1) column."""

    n: np.ndarray          # client count N
    v: np.ndarray          # V
    lam: np.ndarray        # lambda
    ell: np.ndarray        # bits per upload
    bw: np.ndarray         # bandwidth B
    n0: np.ndarray         # noise power N0
    p_max: np.ndarray      # Pmax
    p_bar: np.ndarray      # Pbar
    q_floor: np.ndarray    # floor of q


def _c(x, dtype):
    return np.asarray(x, dtype=dtype)


def lambert_w0(z, dtype):
    """W0(z) for z >= 0 by Halley's iteration from log(1 + z)."""
    one, two = _c(1, dtype), _c(2, dtype)
    z = _c(z, dtype)
    w = _c(np.log1p(z.astype(np.float64)), dtype)
    for _ in range(HALLEY_ITERS):
        ew = _c(np.exp(w), dtype)
        f = _c(w * ew - z, dtype)
        den = _c(ew * (w + one) - _c((w + two) * f, dtype)
                 / (two * w + two), dtype)
        w = _c(w - f / np.where(den == 0, one, den), dtype)
    return w


def _rate(g, p, t: Tenant, dtype):
    one = _c(1, dtype)
    return _c(t.bw * _c(np.log2(one + g * p / t.n0), dtype), dtype)


def _q_of_p(p, g, z, t: Tenant, dtype):
    inv = _c(t.lam * t.ell * t.n / np.maximum(_rate(g, p, t, dtype), EPS)
             + t.n / t.v * z * p, dtype)
    q = _c(_c(1, dtype) / np.sqrt(np.maximum(inv, _c(EPS, dtype))), dtype)
    return _c(np.clip(q, t.q_floor, _c(1, dtype)), dtype)


def objective(q, p, g, z, t: Tenant, dtype=np.float64):
    """Eq. (15)'s per-client f(q, P)."""
    rate = np.maximum(_rate(g, p, t, dtype), _c(EPS, dtype))
    y0 = _c(_c(1, dtype) / (t.n * q) + t.lam * t.ell * q / rate, dtype)
    return _c(t.v * y0 + z * (p * q - t.p_bar), dtype)


def candidates(g, z, t: Tenant, dtype=np.float64):
    """Theorem 2's two candidates per client, the interior optimum and
    P = Pmax, each with its Eq. (15) objective:
    -> (q_int, p_int, f_int), (q_bnd, p_bnd, f_bnd)."""
    g, z = _c(g, dtype), _c(z, dtype)
    a = _c(t.v * t.lam * t.ell * _c(LN2, dtype) / (t.n0 * t.bw), dtype)
    a = _c(a * g / np.maximum(z, _c(EPS, dtype)), dtype)
    w = lambert_w0(_c(np.sqrt(a / _c(4, dtype)), dtype), dtype)
    p_int = _c(t.n0 / g * _c(a / (_c(4, dtype) * np.maximum(w * w, EPS))
                             - _c(1, dtype), dtype), dtype)
    p_int = _c(np.clip(p_int, _c(0, dtype), t.p_max), dtype)
    p_bnd = _c(np.broadcast_to(t.p_max, g.shape), dtype)
    q_int = _q_of_p(p_int, g, z, t, dtype)
    q_bnd = _q_of_p(p_bnd, g, z, t, dtype)
    f_int = objective(q_int, p_int, g, z, t, dtype)
    f_bnd = objective(q_bnd, p_bnd, g, z, t, dtype)
    return (q_int, p_int, f_int), (q_bnd, p_bnd, f_bnd)


def solve(g, z, t: Tenant, dtype=np.float64):
    """Theorem 2: (q, P) per client from gains and queues."""
    (q_int, p_int, f_int), (q_bnd, p_bnd, f_bnd) = candidates(g, z, t, dtype)
    use_int = np.isfinite(f_int) & (f_int <= f_bnd)
    return (np.where(use_int, q_int, q_bnd), np.where(use_int, p_int, p_bnd))


def select(u, q, guarantee_one: bool):
    """I_n = [u_n < q_n]; with none drawn, the client of largest q."""
    sel = u < q
    if guarantee_one:
        none = ~sel.any(axis=-1)
        top = np.argmax(q, axis=-1)
        sel[none, top[none]] = True
    return sel


def lane_account(sel, q, p, g, t: Tenant, dtype=np.float64):
    """Eq. 8 per client: its comm time where selected, and P_n q_n."""
    rate = np.maximum(_rate(g, p, t, dtype), _c(1e-9, dtype))
    return np.where(sel, _c(t.ell / rate, dtype), _c(0, dtype)), p * q


def account(sel, q, p, g, t: Tenant, dtype=np.float64):
    """Eq. 8: TDMA comm time over the selected, and sum_n P_n q_n."""
    t_comm, pq = lane_account(sel, q, p, g, t, dtype)
    return (_c(t_comm.astype(np.float64).sum(-1), dtype),
            _c(pq.astype(np.float64).sum(-1), dtype))


def cast(t: Tenant, dtype) -> Tenant:
    return Tenant(*(_c(x, dtype) for x in t))


def queue_update(z, q, p, p_bar, dtype=np.float64):
    """Eq. 9: Z' = max(Z + P q - Pbar, 0)."""
    return _c(np.maximum(_c(z, dtype) + _c(p, dtype) * _c(q, dtype)
                         - _c(p_bar, dtype), _c(0, dtype)), dtype)


def proposed(u, g, z, t: Tenant, guarantee_one=True, dtype=np.float64):
    """One round of Algorithm 2: -> sel, q, P, t_comm, power, Z'."""
    t = cast(t, dtype)
    q, p = solve(g, z, t, dtype)
    sel = select(_c(u, dtype), q, guarantee_one)
    z_new = queue_update(z, q, p, t.p_bar, dtype)
    t_comm, power = account(sel, q, p, _c(g, dtype), t, dtype)
    return sel, q, p, t_comm, power, z_new

