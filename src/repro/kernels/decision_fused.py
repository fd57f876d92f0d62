"""Fused Pallas decision megakernel: solve + select + Z-update + accounting
summands in one pass over the (N,) client state.

The deployable per-round artifact of the paper is the full decision —
CSI observation -> Theorem-2 power/probability solve -> Bernoulli
selection -> Eq. (9) virtual-queue update -> TDMA accounting — but only
the solve ran in Pallas (``kernels/scheduler_solve.py``); everything else
was stitched XLA around ``fl/decision.py::decision_step``. This kernel
performs the whole post-observation decision in a single tiled pass:

* Theorem-2 solve — the SAME traced helpers as the jnp oracle
  (:func:`repro.core.scheduler.solve_round_coeffs`, including the
  fixed-iteration Halley Lambert-W), evaluated per block. Reusing the
  oracle's exact op sequence (rather than restating it, as the
  solve-only kernel must for its baked-constant signature) is what makes
  the interpreted fused path BITWISE-equal to the stitched composition.
  Compiled for the chip, Mosaic and XLA lower exp/log differently, so
  the two agree to round-off (``chip_smoke.py`` states the tolerance).
* population activity mask (PR-6 semantics) — inactive lanes are forced
  to q = 0 BEFORE selection, so they can never be drawn and contribute
  exactly 0 expected power; their queues still drain by
  ``max(Z - Pbar, 0)`` through the shared Eq. (9) update.
* Bernoulli selection from pre-drawn uniforms (``POLICY_DRAWS`` raws):
  ``sel = u < q``. The guarantee-one fallback needs a global argmax and
  stays OUTSIDE the kernel (see below).
* Eq. (9) Z-queue update ``Z' = max(Z + P q - Pbar, 0)`` via
  :func:`repro.core.scheduler.update_queues_z`.
* the per-lane accounting SUMMANDS: unmasked per-client comm time
  ``ell / max(rate, 1e-9)`` and expected power ``P q`` (validity-masked).

All scalars enter as a packed (14,) float32 RUNTIME OPERAND vector
(:func:`pack_decision_operands`) per the operand contract
(``repro/core/scheduler.py`` module comment) — never baked constants —
so one compiled kernel serves every tenant/config and stays bit-stable
under vmap/shard_map.

What deliberately stays outside the kernel:

* the guarantee-one fallback (global ``argmax(q)``) — a cross-block
  reduction; in the sharded engine it is a cross-SHARD psum/argmax.
* the accounting folds — the kernel emits per-lane summands and the
  caller folds them through ``fl/sharding.py::blocked_total``. Summing
  inside the kernel would re-associate the reduction per block size and
  break the fixed-96-block mesh-invariant accounting contract. (The
  bucket-batched service folds the kernel summands directly; the
  sequential and sharded engine drop-ins recompute them outside from the
  fenced (sel, q, p) instead, because XLA CPU's scalar width-1 ``log2``
  rounds one ulp apart from the vectorized widths the kernel's padded
  blocks always use — an N = 1 engine run would otherwise diverge from
  the stitched oracle.)
* the failed-lane split — Eq. (9) charges Z for every SELECTED client,
  delivered or not (the aggregator spent the airtime), so the kernel's
  Z-update takes no failure input: failed lanes stay charged by
  construction, and delivery filtering happens downstream in the
  training gather.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.scheduler import (SolveCoeffs, solve_round_coeffs,
                                  update_queues_z)

_BLOCK = 1024  # 8 sublanes x 128 lanes
_ROWS = 8      # batched kernel: bucket rows per block (the sublane tile)

# Operand-vector layout: SolveCoeffs' 11 fields in declaration order,
# then the 3 AccountCoeffs fields. Indexing is positional on purpose —
# the pack/unpack pair below is the single source of truth.
N_DECISION_OPS = 14
_N_SOLVE = len(SolveCoeffs._fields)


def pack_decision_operands(solve, acct) -> jax.Array:
    """Pack (SolveCoeffs, AccountCoeffs) into the (14,) f32 operand vector.

    Accepts the ``.solve`` / ``.acct`` halves of a
    :class:`repro.fl.decision.DecisionCoeffs` (host numpy leaves or traced
    scalars — the vector is a runtime operand either way).
    """
    leaves = list(solve) + list(acct)
    assert len(leaves) == N_DECISION_OPS
    return jnp.stack([jnp.asarray(x, jnp.float32) for x in leaves])


def _decision_lanes(ops, gains, z, u, active, valid, fence: bool):
    """The per-lane decision math, shared by the 1D and batched kernels.

    ``ops(i)`` reads operand ``i`` of the (14,) vector for these lanes (an
    SMEM scalar in the 1D kernel, an (8, 1) column of bucket rows in the
    batched one); ``active`` / ``valid`` are optional boolean lanes (None =
    all-on, resolved at trace time so the mask-free kernels carry no dead
    loads). ``fence`` keeps the interpreted body's optimization barrier
    (see below); Mosaic has no lowering for it and fuses nothing across a
    kernel body, so the compiled kernel leaves it out.
    """
    c = SolveCoeffs(*(ops(i) for i in range(_N_SOLVE)))
    ell, bw, n0 = (ops(_N_SOLVE), ops(_N_SOLVE + 1), ops(_N_SOLVE + 2))
    q, p = solve_round_coeffs(gains, z, c)
    if active is not None:
        # population semantics: inactive lanes cannot be selected and
        # contribute zero expected power, but their Z still drains below
        q = jnp.where(active, q, 0.0)
    sel = u < q
    z_new = update_queues_z(z, q, p, c)
    if fence:
        # fence the decision outputs before the accounting summands,
        # exactly where decision_step fences: without it XLA recomputes p
        # inside the tc fusion with different contraction (1-ulp drift vs
        # the stitched path, which derives rate from the materialized p)
        sel, q, p, z_new = jax.lax.optimization_barrier((sel, q, p, z_new))
    # same expression as repro.core.scheduler.coeff_rate, on operand scalars
    rate = bw * jnp.log2(1.0 + gains * p / n0)
    tc = ell / jnp.maximum(rate, 1e-9)  # unmasked: caller gates on final sel
    pq = p * q
    if valid is not None:
        pq = jnp.where(valid, pq, 0.0)
    return sel, q, p, z_new, tc, pq


def _make_kernel(has_active: bool, has_valid: bool, batched: bool,
                 fence: bool):
    def kernel(ops_ref, g_ref, z_ref, u_ref, *refs):
        n_masks = int(has_active) + int(has_valid)
        masks = [r[...] for r in refs[:n_masks]]
        sel_ref, q_ref, p_ref, zn_ref, tc_ref, pq_ref = refs[n_masks:]
        if batched:
            ops = ops_ref[...]
            col = lambda i: ops[:, i:i + 1]  # noqa: E731  (rows, 1)
        else:
            col = lambda i: ops_ref[i]  # noqa: E731  SMEM scalar
        active = masks[0] if has_active else None
        valid = (masks[1] if has_active else masks[0]) if has_valid else None
        sel, q, p, z_new, tc, pq = _decision_lanes(
            col, g_ref[...], z_ref[...], u_ref[...], active, valid, fence)
        sel_ref[...] = sel
        q_ref[...] = q
        p_ref[...] = p
        zn_ref[...] = z_new
        tc_ref[...] = tc
        pq_ref[...] = pq
    return kernel


def _resolve_interpret(interpret):
    """``None`` -> interpret off-TPU. Only the CPU tests rely on this; a
    caller that must run on the chip passes ``interpret=False``."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def _pad_lane(x, pad, fill=0.0):
    if pad == 0:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)],
                   constant_values=jnp.asarray(fill, x.dtype))


def decision_fused(gains: jax.Array, z: jax.Array, u: jax.Array,
                   ops: jax.Array, *, active=None, valid=None,
                   block: int = _BLOCK, interpret: bool | None = None):
    """One fused pass over a flat (N,) client vector.

    gains, z: (N,) float32 state; u: (N,) pre-drawn selection uniforms
    (f32 or, under x64, f64 — compared against q as drawn); ops: the
    (14,) operand vector from :func:`pack_decision_operands`. ``active``
    masks q -> 0 before selection (population activity); ``valid`` masks
    the expected-power summand (bucket/pad accounting). Both optional and
    independent — the engine's population path passes the same mask for
    both, the service passes only ``valid``.

    Returns ``(sel_raw, q, p, z_new, tc, pq)``, each (N,):

    * ``sel_raw`` — ``u < q`` with NO guarantee-one fallback applied;
    * ``tc`` — per-lane comm time ``ell / max(rate, 1e-9)``, UNMASKED so
      a guarantee-forced lane still gets its airtime; the caller applies
      ``where(sel_final, tc, 0)`` and folds through ``blocked_total``;
    * ``pq`` — per-lane expected power ``P q`` (validity-masked).

    Pad hygiene mirrors ``scheduler_solve``: internal padding to a block
    multiple uses gains = 1.0 / Z = 0 (finite solve), u = 2.0 (never
    selected), masks False, and is sliced off before returning.
    ``interpret=None`` auto-selects interpret mode off-TPU; ``block`` is
    value-invariant (tests pin bitwise equality across overrides).
    """
    if block <= 0:
        raise ValueError(f"block must be positive, got {block}")
    interpret = _resolve_interpret(interpret)
    assert gains.shape == z.shape == u.shape and gains.ndim == 1
    n_real = gains.shape[0]
    if n_real == 0:
        raise ValueError("decision_fused needs at least one client")
    pad = (-n_real) % block
    lanes = [_pad_lane(gains.astype(jnp.float32), pad, 1.0),
             _pad_lane(z.astype(jnp.float32), pad),
             _pad_lane(u, pad, 2.0)]
    for m in (active, valid):
        if m is not None:
            assert m.shape == gains.shape
            lanes.append(_pad_lane(m, pad, False))
    n_pad = lanes[0].shape[0]
    bs = pl.BlockSpec((block,), lambda i: (i,))
    obs = pl.BlockSpec(memory_space=pltpu.SMEM)
    outs = pl.pallas_call(
        _make_kernel(active is not None, valid is not None, batched=False,
                     fence=interpret),
        grid=(n_pad // block,),
        in_specs=[obs] + [bs] * len(lanes),
        out_specs=[bs] * 6,
        out_shape=[jax.ShapeDtypeStruct((n_pad,), jnp.bool_)]
        + [jax.ShapeDtypeStruct((n_pad,), jnp.float32)] * 5,
        interpret=interpret,
        name="decision_fused",
    )(ops, *lanes)
    return tuple(o[:n_real] for o in outs)


def decision_fused_batched(gains: jax.Array, z: jax.Array, u: jax.Array,
                           ops: jax.Array, *, valid=None,
                           block: int = _BLOCK,
                           interpret: bool | None = None):
    """Bucket-batched fused decision for the service: (B, N) rows, one
    (14,) operand row per bucket slot.

    Pallas calls do not batch under ``vmap``, so the service's fused path
    uses this natively 2D grid and vmaps only the (cheap) stitched
    guarantee/accounting epilogue. Each block holds 8 bucket rows (B pads
    to a multiple of 8) and ``block`` lanes — or the whole row when N <=
    ``block``, which the (8, 128) tiling rule admits as a full dimension.
    The rows' operands arrive as an (8, 14) block of ``ops`` (B, 14) and
    broadcast along the lanes as (8, 1) columns; heterogeneous tenants
    batch together because coefficients are runtime operands.

    Same returns/hygiene as :func:`decision_fused`, batched: each output
    is (B, N). Pad rows repeat the last operand row and carry the lane pad
    fills, so they stay finite, and are sliced off. The service does NOT
    activity-mask q (pads are neutralised by gains = 0 -> q = q_floor and
    raw = 2.0), so only ``valid`` exists.
    """
    if block <= 0:
        raise ValueError(f"block must be positive, got {block}")
    interpret = _resolve_interpret(interpret)
    assert gains.shape == z.shape == u.shape and gains.ndim == 2
    b, n_real = gains.shape
    assert ops.shape == (b, N_DECISION_OPS)
    if n_real == 0 or b == 0:
        raise ValueError("decision_fused_batched needs a non-empty bucket")
    lane_block = min(block, n_real)
    pad = (-n_real) % lane_block
    pad_b = (-b) % _ROWS

    def pad2(x, fill):
        return jnp.pad(_pad_lane(x, pad, fill), [(0, pad_b), (0, 0)],
                       constant_values=jnp.asarray(fill, x.dtype))

    lanes = [pad2(gains.astype(jnp.float32), 1.0),
             pad2(z.astype(jnp.float32), 0.0), pad2(u, 2.0)]
    if valid is not None:
        assert valid.shape == gains.shape
        lanes.append(pad2(valid, False))
    ops = jnp.pad(ops.astype(jnp.float32), [(0, pad_b), (0, 0)], mode="edge")
    b_pad, n_pad = lanes[0].shape
    bs = pl.BlockSpec((_ROWS, lane_block), lambda r, i: (r, i))
    obs = pl.BlockSpec((_ROWS, N_DECISION_OPS), lambda r, i: (r, 0))
    outs = pl.pallas_call(
        _make_kernel(False, valid is not None, batched=True,
                     fence=interpret),
        grid=(b_pad // _ROWS, n_pad // lane_block),
        in_specs=[obs] + [bs] * len(lanes),
        out_specs=[bs] * 6,
        out_shape=[jax.ShapeDtypeStruct((b_pad, n_pad), jnp.bool_)]
        + [jax.ShapeDtypeStruct((b_pad, n_pad), jnp.float32)] * 5,
        interpret=interpret,
        name="decision_fused_batched",
    )(ops, *lanes)
    return tuple(o[:b, :n_real] for o in outs)
