"""Ahead-of-time compiles of the decision kernels for a described TPU v5e.

Interpret mode on the CPU runs a kernel's math but not the chip's
compiler, which refuses what the interpreter accepts (a scan it cannot
count, a primitive with no Mosaic lowering, blocks off the (8, 128)
tiling). These tests compile each kernel of the main path at real sizes
for a v5e chip that is described, not attached, and check that the
compiled program holds the chip kernel (``tpu_custom_call``) under the
kernel's own name, the name a profiler trace gives its device time:

* ``decision_fused`` at N = 10^6, with and without the activity/validity
  masks (the engines' fused decision);
* ``decision_fused_batched`` at one service bucket shape per width
  32 / 128 / 512 (the ``solver="pallas_fused"`` service);
* ``scheduler_solve`` at N = 10^6.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decision_fused import (N_DECISION_OPS, decision_fused,
                                          decision_fused_batched)
from repro.kernels.scheduler_solve import scheduler_solve

N = 1_000_000


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one, so keep the cache out of these
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _shape(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _holds_kernel(text, name):
    """The compiled program holds a Mosaic call named ``name``."""
    return re.search(rf'%{name}(\.\d+)? = .*custom_call_target='
                     r'"tpu_custom_call"', text) is not None


@pytest.mark.parametrize("masked", [False, True])
def test_decision_fused_compiles(one_chip, masked):
    lanes = [_shape(one_chip, (N,)) for _ in range(3)]
    ops = _shape(one_chip, (N_DECISION_OPS,))
    if masked:
        masks = [_shape(one_chip, (N,), jnp.bool_) for _ in range(2)]

        def fn(g, z, u, o, a, v):
            return decision_fused(g, z, u, o, active=a, valid=v,
                                  interpret=False)
    else:
        masks = []

        def fn(g, z, u, o):
            return decision_fused(g, z, u, o, interpret=False)
    assert _holds_kernel(_compiled_text(fn, *lanes, ops, *masks),
                         "decision_fused")


@pytest.mark.parametrize("width,batch", [(32, 64), (128, 8), (512, 3)])
def test_decision_fused_batched_compiles(one_chip, width, batch):
    lanes = [_shape(one_chip, (batch, width)) for _ in range(3)]
    ops = _shape(one_chip, (batch, N_DECISION_OPS))
    valid = _shape(one_chip, (batch, width), jnp.bool_)

    def fn(g, z, u, o, v):
        return decision_fused_batched(g, z, u, o, valid=v, interpret=False)

    assert _holds_kernel(_compiled_text(fn, *lanes, ops, valid),
                         "decision_fused_batched")


def test_scheduler_solve_compiles(one_chip):
    def fn(g, z):
        return scheduler_solve(g, z, n=N, v=1000.0, lam=10.0,
                               ell=32 * 555178.0, bandwidth=22e6, noise=1.0,
                               p_max=100.0, p_bar=1.0, interpret=False)

    text = _compiled_text(fn, _shape(one_chip, (N,)), _shape(one_chip, (N,)))
    assert _holds_kernel(text, "scheduler_solve")
