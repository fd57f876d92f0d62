"""Public jit'd wrappers around the Pallas kernels.

Dispatch policy: on CPU (this container) the kernels execute in interpret
mode for validation, but the model zoo calls the `*_auto` entry points which
default to the pure-jnp reference path (fast on CPU, identical math). On a
TPU backend the auto paths flip to the compiled Pallas kernels.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.ssd_scan import ssd_scan

__all__ = ["flash_attention", "ssd", "ssd_decode_step", "attention_auto",
           "ssd_auto", "on_tpu"]


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def flash_attention(q, k, v, *, causal=True, window=None, scale=None,
                    block_q=128, block_k=128, interpret=None):
    """(BH, Sq, D) flash attention via the Pallas kernel (interpret on CPU)."""
    if interpret is None:
        interpret = not on_tpu()
    return flash_attention_bhsd(q, k, v, causal=causal, window=window,
                                scale=scale, block_q=block_q, block_k=block_k,
                                interpret=interpret)


def attention_auto(q, k, v, *, causal=True, window=None, scale=None):
    """Model-zoo entry point: Pallas on TPU, jnp oracle elsewhere."""
    if on_tpu():
        return flash_attention(q, k, v, causal=causal, window=window,
                               scale=scale, interpret=False)
    return _ref.attention_ref(q, k, v, causal=causal, window=window,
                              scale=scale)


def ssd(x, dt, a, bm, cm, *, chunk=128, interpret=None):
    """Chunked SSD via the Pallas kernel; pads S to a chunk multiple."""
    if interpret is None:
        interpret = not on_tpu()
    s = x.shape[1]
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        bm = jnp.pad(bm, ((0, 0), (0, pad), (0, 0)))
        cm = jnp.pad(cm, ((0, 0), (0, pad), (0, 0)))
    y = ssd_scan(x, dt, a, bm, cm, chunk=chunk, interpret=interpret)
    return y[:, :s]


def ssd_auto(x, dt, a, bm, cm, *, chunk=128):
    """Model-zoo entry point: Pallas on TPU, sequential-scan oracle elsewhere."""
    if on_tpu():
        return ssd(x, dt, a, bm, cm, chunk=chunk, interpret=False)
    y, _ = _ref.ssd_ref(x, dt, a, bm, cm)
    return y


def ssd_decode_step(h, xt, dtt, a, bt, ct):
    """Single-token SSD recurrence for serving.

    h (b,h,n,p) carried state; xt (b,h,p); dtt (b,h); a (h,); bt/ct (b,n).
    Returns (y_t (b,h,p), new h).
    """
    decay = jnp.exp(dtt.astype(jnp.float32) * a.astype(jnp.float32)[None, :])
    upd = jnp.einsum("bn,bh,bhp->bhnp", bt.astype(jnp.float32),
                     dtt.astype(jnp.float32), xt.astype(jnp.float32))
    h = decay[..., None, None] * h + upd
    y = jnp.einsum("bn,bhnp->bhp", ct.astype(jnp.float32), h)
    return y.astype(xt.dtype), h
