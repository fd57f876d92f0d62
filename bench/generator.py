"""The general traffic generator: tenants, their requests and the flush
schedule, from a configuration, a traffic file and a seed.

Everything random is drawn from ``numpy.random.default_rng(seed)`` in a
fixed order, so a seed gives the same traffic on every run. A
configuration lists its tenants (``tenants``), each one FL deployment with its client
count, upload size, lambda and the Rayleigh scales of its clients; a
request carries one round's channel gains, drawn by the paper's model
(|h|^2 = -2 sigma^2 ln u, clipped to the modulation band), and the
selection uniforms.
"""

from __future__ import annotations

from typing import List, NamedTuple

import numpy as np


class TenantDraw(NamedTuple):
    name: str
    n: int
    policy: str
    V: float
    lam: float
    ell: float
    p_max: float
    sigma: np.ndarray      # (n,) Rayleigh scale of each client


def sigmas(counts, values) -> np.ndarray:
    """Per-client Rayleigh scales: ``counts[i]`` clients at ``values[i]``."""
    return np.concatenate([np.full(int(c), v, np.float32)
                           for c, v in zip(counts, values)])


def make_tenants(cfg: dict) -> List[TenantDraw]:
    """The configuration's tenants, in the order it lists them. Each entry
    names its ``n_clients``, ``model_bits``, ``lam`` and its clients'
    ``sigma_counts`` and ``sigma_values``; V, Pmax and the policy are the
    configuration's."""
    out = []
    for i, t in enumerate(cfg["tenants"]):
        sig = sigmas(t["sigma_counts"], t["sigma_values"])
        if sig.size != t["n_clients"]:
            raise ValueError(f"tenant {i}: sigma counts sum to {sig.size}, "
                             f"not {t['n_clients']}")
        out.append(TenantDraw(t["name"], int(t["n_clients"]), cfg["policy"],
                              float(cfg["V"]), float(t["lam"]),
                              float(t["model_bits"]), float(cfg["p_max"]),
                              sig))
    return out


def gain_band(cfg: dict):
    """The modulation band of the gains: the least at which the lowest
    spectral efficiency is reached at Pmax, the most at which the highest
    is reached at Pbar."""
    n0 = cfg["noise_power"]
    lo = (2.0 ** cfg["min_spectral_eff"] - 1.0) * n0 / cfg["p_max"]
    hi = (2.0 ** cfg["max_spectral_eff"] - 1.0) * n0 / cfg["p_bar"]
    return lo, hi


def make_payloads(cfg: dict, tenants: List[TenantDraw], count: int,
                  rng: np.random.Generator):
    """``count`` request payloads per tenant: one round's gains by the
    paper's model and one selection uniform per client. -> (gains, raws),
    one (count, n) float32 array of each per tenant."""
    lo, hi = gain_band(cfg)
    gains, raws = [], []
    for t in tenants:
        if t.policy != "proposed":
            raise ValueError(f"no payload for policy {t.policy!r}")
        u = rng.uniform(1e-12, 1.0, (count, t.n)).astype(np.float32)
        g = -2.0 * (t.sigma.astype(np.float64) ** 2) * np.log(u)
        gains.append(np.clip(g, lo, hi).astype(np.float32))
        raws.append(rng.random((count, t.n), dtype=np.float32))
    return gains, raws


def flush_schedule(n_tenants: int, size, flushes: int,
                   rng: np.random.Generator) -> List[np.ndarray]:
    """Which tenants each flush holds.

    * ``"all"``: every tenant, in registration order (every deployment's
      round in one flush);
    * ``"each"``: one tenant a flush, each tenant once in every run of
      ``n_tenants`` flushes, in an order drawn from the seed;
    """
    if size == "all":
        every = np.arange(n_tenants)
        return [every] * flushes
    if size == "each":
        order = np.concatenate([rng.permutation(n_tenants) for _ in range(
            -(-flushes // n_tenants))])[:flushes]
        return [order[i:i + 1] for i in range(flushes)]
    raise ValueError(f"no flush size {size!r}")
