"""Property pin: blocked_total == blocked_total_sharded for ANY split.

The mesh-invariant accounting reduce (``fl/sharding.py``) is the numeric
keystone of every sharded engine: a float32 sum over the client axis that
associates as ACCOUNT_BLOCKS fixed blocks regardless of how many devices
the axis is sharded over. This module pins the invariant DIRECTLY — not
through a simulation — for arbitrary shard splits:

* an *emulated* split: slice the padded contribution vector into D
  contiguous shards on the host, run each shard through the same
  ``block_partials`` the shard_map body runs, concatenate in global block
  order (what ``all_gather`` produces), and fold. Valid for every divisor
  D of ACCOUNT_BLOCKS — no devices needed, so the property covers splits
  far wider than the CI mesh (up to 96 shards).
* a *real* ``shard_map`` split on a ('client',) mesh for every feasible
  device count, pinning that the emulation IS what the collective path
  computes.

Agreement is EXACT (bit-for-bit), not approximate: same partials, same
fold order, by construction. Edge cases the property must hold through:
ragged final blocks (N not a multiple of ACCOUNT_BLOCKS pads with exact
zeros), all-masked lanes (all-zero contributions), subnormals, huge
magnitude spread (catastrophic-cancellation bait), and negative values.

Runs as a hypothesis property when hypothesis is installed
(tests/_hyp.py) AND as a deterministic fixed-seed sweep either way.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.fl.sharding import (ACCOUNT_BLOCKS, block_partials, blocked_total,
                               blocked_total_sharded, padded_len, shard_map)

DIVISORS = [d for d in range(1, ACCOUNT_BLOCKS + 1)
            if ACCOUNT_BLOCKS % d == 0]


def _emulated_sharded_total(contrib: np.ndarray, n_shards: int) -> float:
    """blocked_total_sharded's association, computed shard by shard on
    the host: per-shard block partials, concatenated in global block
    order, folded by the same unrolled chain."""
    from repro.fl.sharding import _fold_partials

    n_pad = padded_len(contrib.shape[0])
    padded = np.zeros((n_pad,), np.float32)
    padded[:contrib.shape[0]] = contrib
    per = n_pad // n_shards
    parts = [
        np.asarray(block_partials(jnp.asarray(padded[i * per:(i + 1) * per]),
                                  ACCOUNT_BLOCKS // n_shards))
        for i in range(n_shards)
    ]
    full = jnp.asarray(np.concatenate(parts))
    return float(_fold_partials(full, ACCOUNT_BLOCKS))


def _check_all_splits(contrib: np.ndarray):
    ref = float(blocked_total(jnp.asarray(contrib)))
    for d in DIVISORS:
        got = _emulated_sharded_total(contrib, d)
        assert np.float32(got) == np.float32(ref) or (
            np.isnan(got) and np.isnan(ref)), \
            f"split {d}: {got!r} != {ref!r} (n={contrib.shape[0]})"


# --------------------------------------------------- deterministic sweep

# Lengths exercising ragged final blocks (not multiples of 96), exact
# multiples, tiny vectors (single partial), and the parity-suite N.
LENGTHS = (1, 5, 48, 96, 100, 191, 192, 1000)


@pytest.mark.parametrize("n", LENGTHS)
def test_fixed_seed_sweep(n):
    """Every divisor split agrees bitwise, for adversarial value mixes."""
    rng = np.random.default_rng(n)
    cases = [
        rng.normal(0, 1, n).astype(np.float32),
        # huge magnitude spread: reassociation WOULD change the sum
        (rng.normal(0, 1, n) * 10.0 ** rng.integers(-20, 20, n)
         ).astype(np.float32),
        np.zeros((n,), np.float32),                   # all-masked lanes
        np.full((n,), 1e-38, np.float32),             # near-subnormal
        -np.abs(rng.normal(0, 100, n)).astype(np.float32),
    ]
    for contrib in cases:
        _check_all_splits(contrib)


def test_reassociation_would_differ():
    """Sanity: the property is non-trivial — a naive np.float32 re-sum of
    the magnitude-spread case DOES differ from fold order, so bitwise
    agreement across splits is not vacuous."""
    rng = np.random.default_rng(7)
    n = 1000
    contrib = (rng.normal(0, 1, n) * 10.0 ** rng.integers(-10, 10, n)
               ).astype(np.float32)
    fwd = np.float32(0.0)
    for v in contrib:
        fwd = np.float32(fwd + v)
    rev = np.float32(0.0)
    for v in contrib[::-1]:
        rev = np.float32(rev + v)
    # Not an invariant of float32 addition in general; if these happen to
    # collide the draw is too tame for the sweep above to mean much.
    assert fwd != rev


# ------------------------------------------------------ real shard_map leg

def test_real_shard_map_matches_emulation():
    """The actual collective path (shard_map + all_gather) computes the
    emulated association bit-for-bit, for every feasible device count."""
    from jax.sharding import Mesh, PartitionSpec as P

    n_dev = len(jax.devices())
    feasible = [d for d in DIVISORS if d <= n_dev]
    rng = np.random.default_rng(3)
    for n in (48, 100, 192):
        contrib = (rng.normal(0, 1, n) * 10.0 ** rng.integers(-8, 8, n)
                   ).astype(np.float32)
        n_pad = padded_len(n)
        padded = np.zeros((n_pad,), np.float32)
        padded[:n] = contrib
        ref = float(blocked_total(jnp.asarray(contrib)))
        for d in feasible:
            mesh = Mesh(np.array(jax.devices()[:d]), ("client",))
            total = shard_map(
                lambda c, _d=d: blocked_total_sharded(c, "client", _d),
                mesh=mesh, in_specs=(P("client"),), out_specs=P())(
                    jnp.asarray(padded))
            assert float(total) == ref, (d, n)
            assert _emulated_sharded_total(contrib, d) == ref, (d, n)


# -------------------------------------------------------- hypothesis leg

# hypothesis takes only float32-representable bounds at width=32
F32_1E30 = float(np.float32(1e30))

@settings(max_examples=40, deadline=None)
@given(st.data())
def test_property_arbitrary_vectors(data):
    """Hypothesis: arbitrary finite f32 vectors, arbitrary length, agree
    bitwise across every divisor split (including ragged final blocks)."""
    n = data.draw(st.integers(min_value=1, max_value=500), label="n")
    vals = data.draw(
        st.lists(st.floats(min_value=-F32_1E30, max_value=F32_1E30, width=32,
                           allow_nan=False, allow_infinity=False),
                 min_size=n, max_size=n),
        label="vals")
    _check_all_splits(np.asarray(vals, np.float32))
