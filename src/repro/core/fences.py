"""Fusion fences: ``pin`` values into closed XLA optimization islands.

The scenario grid's parity contract (tests/test_grid.py) requires a
channel/policy step to produce identical float32 bits in every compilation
context — closed-over constant sigmas vs a traced table row, a standalone
chunk executable vs the grid's one-program trace. XLA freely reassociates
constant factors and refuses op chains per context, drifting results by a
ulp per round; ``jax.lax.optimization_barrier`` pins a value so no op can
be fused, hoisted, or folded across it.
"""

from __future__ import annotations

import jax


def pin(x):
    """Pin a value (or pytree) into its own XLA fusion island."""
    return jax.lax.optimization_barrier(x)

