"""Principal-branch Lambert W function, pure JAX.

Algorithm 2 of the paper needs W0(sqrt(A/4)) with A >= 0 (Eq. 16), i.e. only
the principal branch on the non-negative real axis. We implement W0 for
z >= 0 with a log-based initial guess plus Halley iterations, which converges
to float64/float32 round-off in <= 6 iterations on [0, 1e30].

This is elementwise and jit/vmap/grad friendly (fixed iteration count, no
data-dependent control flow), so it vectorizes trivially over all N clients.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# Halley is cubic: from the piecewise initial guess, 3 iterations reach
# float32 round-off and 4 reach float64 round-off over [0, 1e12] (checked
# against scipy.special.lambertw; tests/test_lambertw.py covers the domain).
# The solve is ~40% Lambert-W on CPU, so the iteration count is a hot knob.
_HALLEY_ITERS = 4


def _initial_guess(z: jax.Array) -> jax.Array:
    """Piecewise initial guess for W0(z), z >= 0.

    Near 0:   W0(z) ~ z (1 - z)          (series)
    Large z:  W0(z) ~ log z - log log z  (asymptotic)
    """
    z = jnp.asarray(z)
    # Guard log of <=1 values; the branch is only selected where valid.
    safe = jnp.maximum(z, jnp.asarray(2.718282, z.dtype))
    lz = jnp.log(safe)
    llz = jnp.log(lz)
    asym = lz - llz + llz / lz
    series = z * (1.0 - z + 1.5 * z * z)
    return jnp.where(z < 1.0, series, asym)


def lambertw0(z: jax.Array) -> jax.Array:
    """W0(z) for real z >= 0 (the paper only evaluates W0 at sqrt(A/4) >= 0).

    Returns w with w * exp(w) == z. NaN-free for z >= 0; z < 0 is clamped to 0
    (callers in Algorithm 2 never produce negative arguments).
    """
    z = jnp.asarray(z)
    dt = z.dtype if jnp.issubdtype(z.dtype, jnp.floating) else jnp.float32
    z = jnp.maximum(z.astype(dt), 0.0)
    w = _initial_guess(z).astype(dt)

    def halley(_, w):
        ew = jnp.exp(w)
        f = w * ew - z
        # Halley: w' = w - f / (ew*(w+1) - (w+2) f / (2w+2))
        denom = ew * (w + 1.0) - (w + 2.0) * f / (2.0 * w + 2.0)
        # denom > 0 for w >= 0; protect anyway.
        step = f / jnp.where(jnp.abs(denom) < 1e-30, 1e-30, denom)
        return w - step

    # a fori_loop (not a scan): the Pallas TPU lowering accepts only a
    # counted loop inside the fused decision kernel
    return jax.lax.fori_loop(0, _HALLEY_ITERS, halley, w)
