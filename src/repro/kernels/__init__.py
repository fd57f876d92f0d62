"""Pallas TPU kernels for the framework's compute hot spots.

Two kernels, each with a BlockSpec-tiled `pl.pallas_call` implementation,
a jit'd wrapper in ops.py, and a pure-jnp oracle in ref.py:

* ``flash_attention`` — online-softmax attention with VMEM scratch
  accumulators (used by 8 of the 10 assigned architectures).
* ``ssd_scan`` — Mamba-2 chunked state-space-duality scan (mamba2, jamba).

Plus the fused decision megakernel (``decision_fused``), the paper's
Theorem-2 decision for the ``proposed`` policy: one pass computing
solve + Bernoulli selection + Eq. (9) Z-update + accounting summands,
bitwise-equal to the stitched ``fl/decision.py::decision_step`` because
it reuses the jnp oracle's traced helpers on runtime-operand scalars.
"""

from repro.kernels import ops, ref
from repro.kernels.decision_fused import (N_DECISION_OPS, decision_fused,
                                          decision_fused_batched,
                                          pack_decision_operands)
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.ssd_scan import ssd_scan

__all__ = ["ops", "ref", "flash_attention_bhsd", "ssd_scan",
           "decision_fused", "decision_fused_batched",
           "pack_decision_operands", "N_DECISION_OPS"]
