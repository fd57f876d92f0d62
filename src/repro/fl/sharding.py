"""Client/participant mesh utilities shared by the sharded engines.

Two layers live here:

* ``shard_map`` with the replication check off (every caller needs it off
  because the bodies close over unpartitioned constants).
* the **mesh-invariant blocked reduction** behind the client-sharded
  scheduling path's accounting contract: a float32 sum over the (N,)
  client axis whose ASSOCIATION does not depend on how many devices the
  axis is sharded over. The sum is always associated as ``ACCOUNT_BLOCKS``
  fixed contiguous blocks — block partials first, then one fixed-order
  reduce over the (ACCOUNT_BLOCKS,) partial vector — and every stage is
  fenced with ``optimization_barrier`` so XLA builds the identical
  reduction graph in every surrounding program. A D-device shard of the
  client axis owns ``ACCOUNT_BLOCKS / D`` whole blocks, computes their
  partials locally, and an ``all_gather`` reassembles the (ACCOUNT_BLOCKS,)
  vector in global block order — so the sequential engine (D absent), the
  mesh-1 shard, and any wider mesh all add the same numbers in the same
  order. At mesh size 1 this is bit-for-bit the sequential reduce; across
  mesh widths the association is identical but the EMISSION of the
  per-lane summand chains is not guaranteed (LLVM inlines transcendental
  expansions and contracts multiplies into adds differently per kernel
  shape — unavoidable since the decision layer's coefficients became
  runtime operands for the scheduler service's bitwise contract, see
  repro/core/scheduler.py), so cross-mesh float accounting agrees to
  ~1 ulp. Integer accounting (n_selected, packed indices) is exact in
  practice and pinned by the suite's fixed seeds — though in principle a
  Bernoulli draw could land inside the ~1 ulp cross-mesh q drift and
  flip one selection (probability ~2^-23 per drifting lane-round)
  (tests/test_client_sharded.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core.fences import pin

# Fixed association width of the accounting reduce. Constant across mesh
# sizes BY DESIGN (cross-mesh bit-equality needs every mesh to add the same
# block partials); 96 is divisible by 1/2/3/4/6/8/12/16/24/32/48/96, so the
# CI 8-virtual-device mesh AND the power-of-two TPU slices (16, 32) the
# Pallas path targets all divide it. Changing this constant changes every
# engine trajectory by ~1 ulp — it is part of the numeric contract, not a
# tuning knob.
ACCOUNT_BLOCKS = 96


def shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the replication check off."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def make_mesh2d(client_shards: int, part_shards: int,
                devices=None) -> Mesh:
    """The ONE shared 2D device mesh ``('client', 'part')`` both sharded
    stages of a composed round ride.

    ``SimConfig(client_shards=Dc, participant_shards=Dp)`` reshapes the
    first ``Dc * Dp`` devices to ``(Dc, Dp)``. The composition works
    because a ``shard_map`` whose specs name only one mesh axis is
    replicated over the other: the scheduling shard_map keeps its
    ``P('client')`` specs (every 'part' column runs an identical copy of
    the per-shard schedule program) and the participant-training shard_map
    keeps its ``P('part')`` specs (every 'client' row trains the same
    packed participants) — so each stage's per-device program and
    collectives are EXACTLY the 1D paths', which is what carries the
    per-mesh numeric contract over unchanged: ``(Dc, 1)`` matches the old
    ``client_shards=Dc`` run, ``(1, Dp)`` the old ``participant_shards=Dp``
    run, and ``(1, 1)`` stays bitwise-equal to ``run_simulation_scan``
    (tests/test_mesh2d.py). The only cross-stage traffic is the
    all-gathered <= m_cap participant index pack, replicated on exit from
    the 'client' stage and re-consumed sharded by the 'part' stage.

    Either extent may be 1 (0 is treated as 1): the degenerate meshes ARE
    the 1D paths on one shared mesh object.
    """
    devices = list(devices if devices is not None else jax.devices())
    dc = max(1, int(client_shards))
    dp = max(1, int(part_shards))
    if dc * dp > len(devices):
        raise ValueError(
            f"mesh ({dc}, {dp}) = {dc * dp} devices, but only "
            f"{len(devices)} are available (client_shards * "
            f"participant_shards must fit the device count)")
    if ACCOUNT_BLOCKS % dc:
        raise ValueError(
            f"client_shards={dc} must divide ACCOUNT_BLOCKS="
            f"{ACCOUNT_BLOCKS} (the fixed association width of the exact "
            f"accounting reduce; see blocked_total)")
    return Mesh(np.array(devices[:dc * dp]).reshape(dc, dp),
                ("client", "part"))


def padded_len(n: int, n_blocks: int = ACCOUNT_BLOCKS) -> int:
    """The client-axis length after padding to whole accounting blocks."""
    return n + (-n) % n_blocks


def block_partials(contrib: jax.Array, n_blocks: int) -> jax.Array:
    """Per-block partial sums of a (n_blocks * L,) contribution vector.

    The pins on both sides are load-bearing: they keep the row reduction an
    isolated XLA island, so a (96, L) sequential reshape and a (12, L)
    per-shard reshape of the same lanes reduce with identical association
    (verified bit-for-bit by the client-sharded parity suite).
    """
    return pin(jnp.sum(pin(contrib).reshape(n_blocks, -1), axis=1))


def _fold_partials(partials: jax.Array, n_blocks: int) -> jax.Array:
    """Left-fold the (n_blocks,) partials with an explicit add chain.

    A ``jnp.sum`` here would leave the association to the reduce lowering,
    which XLA picks per surrounding program (observed: the same 24-element
    reduce compiles to different f32 bits inside vs outside a shard_map).
    An unrolled chain of scalar adds has no such freedom — XLA does not
    reassociate explicit float adds — so the fold is identical in every
    context by construction. n_blocks is small and fixed; the unroll is
    under a hundred scalar adds.
    """
    partials = pin(partials)
    total = partials[0]
    for i in range(1, n_blocks):
        total = total + partials[i]
    return pin(total)


def blocked_total(contrib: jax.Array,
                  n_blocks: int = ACCOUNT_BLOCKS) -> jax.Array:
    """Mesh-invariant f32 total of per-client contributions (N,) -> ().

    Pads with exact zeros to whole blocks (+0.0 terms cannot change any
    partial), then reduces block partials in fixed order. This is THE
    accounting reduction of every engine: the scan/grid round core calls it
    directly, and :func:`blocked_total_sharded` computes the identical
    association from per-shard slices.
    """
    n = contrib.shape[0]
    pad = (-n) % n_blocks
    if pad:
        contrib = jnp.concatenate(
            [contrib, jnp.zeros((pad,), contrib.dtype)])
    return _fold_partials(block_partials(contrib, n_blocks), n_blocks)


def blocked_total_sharded(contrib_local: jax.Array, axis_name: str,
                          n_shards: int,
                          n_blocks: int = ACCOUNT_BLOCKS) -> jax.Array:
    """:func:`blocked_total` from inside a client-sharded ``shard_map`` body.

    ``contrib_local`` is this shard's (n_padded / n_shards,) slice — already
    padded, so each shard owns ``n_blocks / n_shards`` whole blocks. The
    only bytes that cross devices are the (n_blocks,) block partials.
    """
    part = block_partials(contrib_local, n_blocks // n_shards)
    full = jax.lax.all_gather(part, axis_name).reshape(n_blocks)
    return _fold_partials(full, n_blocks)


def pad_client_axis(x: jax.Array, n_pad: int, fill, axis: int = -1):
    """Pad the client axis of ``x`` up to ``n_pad`` lanes with ``fill``.

    The client-sharded round pads every (N,)-shaped operand on entry (and
    slices the state back to (N,) on exit) so the carry layout stays
    identical to the sequential engine's.
    """
    axis = axis % x.ndim
    n = x.shape[axis]
    if n == n_pad:
        return x
    shape = x.shape[:axis] + (n_pad - n,) + x.shape[axis + 1:]
    return jnp.concatenate([x, jnp.full(shape, fill, x.dtype)], axis=axis)
