"""Pure-jnp oracles for every Pallas kernel (the allclose targets in tests).

These are also the implementations the model zoo uses by default on CPU
(the kernels run in interpret mode only for validation; on a real TPU the
ops.py wrappers flip to compiled Pallas).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_NEG_INF = -1e30


def attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  causal: bool = True, window: int | None = None,
                  scale: float | None = None) -> jax.Array:
    """Dense softmax attention over (BH, Sq, D)/(BH, Sk, D). fp32 softmax."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    if scale is None:
        scale = float(d) ** -0.5
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    q_pos = jnp.arange(sq)[:, None]
    k_pos = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), bool)
    if causal:
        # decode case (sq < sk): queries sit at the END of the kv window.
        offset = sk - sq
        mask &= k_pos <= (q_pos + offset)
        if window is not None:
            mask &= k_pos > (q_pos + offset - window)
    elif window is not None:
        mask &= jnp.abs(k_pos - q_pos) < window
    s = jnp.where(mask[None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32)).astype(q.dtype)


def ssd_ref(x: jax.Array, dt: jax.Array, a: jax.Array, bm: jax.Array,
            cm: jax.Array, h0: jax.Array | None = None):
    """Sequential SSD recurrence — the ground truth for the chunked kernel.

    h[t] = exp(dt[t] a) h[t-1] + dt[t] B[t] (x) x[t];  y[t] = C[t] . h[t]
    x (b,s,h,p), dt (b,s,h), a (h,), bm/cm (b,s,n). Returns (y, h_final)
    with h_final (b,h,n,p).
    """
    b, s, h, p = x.shape
    n = bm.shape[-1]
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    af = a.astype(jnp.float32)
    bmf = bm.astype(jnp.float32)
    cmf = cm.astype(jnp.float32)
    if h0 is None:
        h0 = jnp.zeros((b, h, n, p), jnp.float32)

    def step(hstate, inp):
        xt, dtt, bt, ct = inp                      # (b,h,p) (b,h) (b,n) (b,n)
        decay = jnp.exp(dtt * af[None, :])         # (b,h)
        upd = jnp.einsum("bn,bh,bhp->bhnp", bt, dtt, xt)
        hstate = decay[..., None, None] * hstate + upd
        yt = jnp.einsum("bn,bhnp->bhp", ct, hstate)
        return hstate, yt

    inputs = (jnp.moveaxis(xf, 1, 0), jnp.moveaxis(dtf, 1, 0),
              jnp.moveaxis(bmf, 1, 0), jnp.moveaxis(cmf, 1, 0))
    h_final, ys = jax.lax.scan(step, h0, inputs)
    y = jnp.moveaxis(ys, 0, 1).astype(x.dtype)     # (b,s,h,p)
    return y, h_final


def ssd_chunked_ref(x, dt, a, bm, cm, *, chunk: int = 128,
                    h0: jax.Array | None = None, unroll: bool = False):
    """Chunked SSD in pure jnp — same dual-form algorithm as the Pallas
    kernel, vectorized over (batch, heads), returning the final state too
    (used by the serving prefill to seed decode).

    Shapes as in ssd_ref. S must be a multiple of ``chunk`` (callers pad).
    """
    b, s, h, p = x.shape
    n = bm.shape[-1]
    assert s % chunk == 0
    nc = s // chunk
    xf = x.astype(jnp.float32).reshape(b, nc, chunk, h, p)
    dtf = dt.astype(jnp.float32).reshape(b, nc, chunk, h)
    af = a.astype(jnp.float32)
    bmf = bm.astype(jnp.float32).reshape(b, nc, chunk, n)
    cmf = cm.astype(jnp.float32).reshape(b, nc, chunk, n)
    if h0 is None:
        h0 = jnp.zeros((b, h, n, p), jnp.float32)

    t_idx = jnp.arange(chunk)
    causal = t_idx[:, None] >= t_idx[None, :]                  # (L, L)

    def step(state, inp):
        xc, dtc, bc, cc = inp        # (b,L,h,p) (b,L,h) (b,L,n) (b,L,n)
        g = dtc * af                                             # (b,L,h)
        lc = jnp.cumsum(g, axis=1)
        decay = lc[:, :, None, :] - lc[:, None, :, :]            # (b,L,L,h)
        w = jnp.where(causal[None, :, :, None],
                      jnp.exp(jnp.minimum(decay, 0.0)), 0.0)
        scores = jnp.einsum("bln,bmn->blm", cc, bc)              # (b,L,L)
        m = scores[..., None] * w * dtc[:, None, :, :]           # (b,L,L,h)
        y = jnp.einsum("blmh,bmhp->blhp", m, xc)
        y += jnp.einsum("bln,blh,bhnp->blhp", cc, jnp.exp(lc), state)
        carry = jnp.exp(lc[:, -1, :])                            # (b,h)
        bw = jnp.exp(lc[:, -1:, :] - lc) * dtc                   # (b,L,h)
        state = carry[:, :, None, None] * state + jnp.einsum(
            "bln,blh,blhp->bhnp", bc, bw, xc)
        return state, y

    inputs = (jnp.moveaxis(xf, 1, 0), jnp.moveaxis(dtf, 1, 0),
              jnp.moveaxis(bmf, 1, 0), jnp.moveaxis(cmf, 1, 0))
    h_final, ys = jax.lax.scan(step, h0, inputs,
                               unroll=nc if unroll else 1)
    y = jnp.moveaxis(ys, 0, 1).reshape(b, s, h, p).astype(x.dtype)
    return y, h_final

