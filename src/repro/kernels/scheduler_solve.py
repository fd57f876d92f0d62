"""Pallas TPU kernel for the per-client Algorithm-2 closed-form solve.

The Theorem-2 solution is elementwise over clients: given (|h_n|^2, Z_n) and
the scalars (V, lambda, ell, B, N0, Pmax, Pbar, N), emit (q_n, P_n). At MEC
scale (N up to millions of devices on a city-wide deployment) the aggregator
solves all clients each round; this kernel tiles the client vector through
VMEM in 8x128-aligned blocks and evaluates the Lambert-W closed form on the
VPU — one HBM round-trip, no intermediate materialization.

Matches `repro.core.scheduler.solve_round` (the jnp oracle re-exported in
kernels/ref.py) to float32 round-off.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_LN2 = 0.6931471805599453
_EPS = 1e-12
_BLOCK = 1024  # 8 sublanes x 128 lanes


def _halley_w0(z):
    """Principal Lambert-W on z >= 0 — same fixed-iteration scheme as
    repro.core.lambertw, restated with plain ops so it lowers inside Pallas."""
    safe = jnp.maximum(z, 2.718282)
    lz = jnp.log(safe)
    llz = jnp.log(lz)
    w = jnp.where(z < 1.0, z * (1.0 - z + 1.5 * z * z), lz - llz + llz / lz)
    for _ in range(4):  # cubic convergence: 4 from this init is f64-exact
        ew = jnp.exp(w)
        f = w * ew - z
        denom = ew * (w + 1.0) - (w + 2.0) * f / (2.0 * w + 2.0)
        denom = jnp.where(jnp.abs(denom) < 1e-30, 1e-30, denom)
        w = w - f / denom
    return w


def _rate(gains, p, bandwidth, noise):
    return bandwidth * jnp.log2(1.0 + gains * p / noise)


def _q_eq17(p, gains, z, *, n, v, lam, ell, bandwidth, noise, q_floor):
    rate = jnp.maximum(_rate(gains, p, bandwidth, noise), _EPS)
    inv_sq = lam * ell * n / rate + n / v * z * p
    q = jax.lax.rsqrt(jnp.maximum(inv_sq, _EPS))
    return jnp.clip(q, q_floor, 1.0)


def _objective(q, p, gains, z, *, n, v, lam, ell, bandwidth, noise, p_bar):
    rate = jnp.maximum(_rate(gains, p, bandwidth, noise), _EPS)
    y0 = 1.0 / (n * q) + lam * ell * q / rate
    return v * y0 + z * (p * q - p_bar)


def _solve_block(gains, z, *, n, v, lam, ell, bandwidth, noise, p_max, p_bar,
                 q_floor):
    """Branch-free Theorem-2 solve for one block of clients."""
    zs = jnp.maximum(z, _EPS)
    # corrected Eq.16 constant (see repro/core/scheduler.py): ln2, not ln2^2
    a = v * lam * ell * gains * _LN2 / (noise * bandwidth * zs)
    w = _halley_w0(jnp.sqrt(a / 4.0))
    p_int = noise / gains * (a / (4.0 * jnp.maximum(w * w, _EPS)) - 1.0)
    p_int = jnp.clip(p_int, 0.0, p_max)
    kw = dict(n=n, v=v, lam=lam, ell=ell, bandwidth=bandwidth, noise=noise)
    q_int = _q_eq17(p_int, gains, z, q_floor=q_floor, **kw)
    p_bnd = jnp.full_like(gains, p_max)
    q_bnd = _q_eq17(p_bnd, gains, z, q_floor=q_floor, **kw)
    f_int = _objective(q_int, p_int, gains, z, p_bar=p_bar, **kw)
    f_bnd = _objective(q_bnd, p_bnd, gains, z, p_bar=p_bar, **kw)
    use_int = jnp.isfinite(f_int) & (f_int <= f_bnd)
    return (jnp.where(use_int, q_int, q_bnd),
            jnp.where(use_int, p_int, p_bnd))


def _kernel(gains_ref, z_ref, q_ref, p_ref, *, params):
    gains = gains_ref[...]
    z = z_ref[...]
    q, p = _solve_block(gains, z, **params)
    q_ref[...] = q
    p_ref[...] = p


@functools.partial(jax.jit, static_argnames=(
    "n", "v", "lam", "ell", "bandwidth", "noise", "p_max", "p_bar", "q_floor",
    "interpret", "block"))
def scheduler_solve(gains: jax.Array, z: jax.Array, *, n: int, v: float,
                    lam: float, ell: float, bandwidth: float, noise: float,
                    p_max: float, p_bar: float, q_floor: float = 1e-5,
                    interpret: bool | None = None, block: int = _BLOCK):
    """Tiled Pallas evaluation of Theorem 2 over a flat client vector.

    gains, z: (N,) float32. Returns (q, P), each (N,) float32. N is padded to
    a multiple of ``block`` internally; on TPU each block is one VMEM-resident
    (8, 128)-tiled VPU pass. ``interpret=None`` auto-selects: compiled on a
    TPU backend, interpret mode everywhere else — this is what lets the
    simulation engine's ``solver="pallas"`` config run unchanged on CPU.

    Padded-lane hygiene: pad lanes carry gains = 1.0 with Z = 0, which the
    solve maps to finite boundary values (Z = 0 floors to _EPS, the huge
    Lambert-W argument saturates to the P = Pmax branch) — no NaN/inf is
    ever produced that could leak into real lanes through a compiler
    re-association, and the pad is sliced off before returning
    (tests/test_scheduler_solve_pallas.py pins this at every edge size).
    ``block`` may be overridden (e.g. shard-local client slices keep
    interpret-mode CI affordable); on TPU keep it a multiple of the
    8 x 128 = 1024 VPU tile or the compiler will pad each grid step.
    """
    if block <= 0:
        raise ValueError(f"block must be positive, got {block}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    assert gains.shape == z.shape and gains.ndim == 1
    n_real = gains.shape[0]
    if n_real == 0:
        raise ValueError("scheduler_solve needs at least one client")
    pad = (-n_real) % block
    gains_p = jnp.pad(gains.astype(jnp.float32), (0, pad), constant_values=1.0)
    z_p = jnp.pad(z.astype(jnp.float32), (0, pad))
    params = dict(n=float(n), v=float(v), lam=float(lam), ell=float(ell),
                  bandwidth=float(bandwidth), noise=float(noise),
                  p_max=float(p_max), p_bar=float(p_bar),
                  q_floor=float(q_floor))
    grid = (gains_p.shape[0] // block,)
    q, p = pl.pallas_call(
        functools.partial(_kernel, params=params),
        grid=grid,
        in_specs=[pl.BlockSpec((block,), lambda i: (i,)),
                  pl.BlockSpec((block,), lambda i: (i,))],
        out_specs=[pl.BlockSpec((block,), lambda i: (i,)),
                   pl.BlockSpec((block,), lambda i: (i,))],
        out_shape=[jax.ShapeDtypeStruct(gains_p.shape, jnp.float32),
                   jax.ShapeDtypeStruct(gains_p.shape, jnp.float32)],
        interpret=interpret,
        name="scheduler_solve",
    )(gains_p, z_p)
    return q[:n_real], p[:n_real]
