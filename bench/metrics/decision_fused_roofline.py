"""decision_fused_roofline: the one-dimensional fused decision kernel's
share of its roofline over the traced window, in percent.

The least time is the larger of the kernel's bytes over the HBM peak and
its operations over the compute peak (``bench/counts.py``; every client
of every round in the window, ``counters["kernel_bytes"]`` and
``counters["kernel_ops"]``). The kernel's time is the device time of the
operations named ``decision_fused`` or ``decision_fused.<n>`` in the
trace: the ``pallas_call`` of ``kernels/decision_fused.py::
decision_fused``, and not its batched twin (``decision_fused_batched``).
Where no such operation ran (a program without the kernel) it reads
nothing. ``counters["kernel_bound"]`` records which bound applies.
"""

import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import counts  # noqa: E402

KERNEL = re.compile(r"^decision_fused(\.\d+)?$")


def read(ctx):
    if ctx.trace is None or not ctx.counters.get("kernel_bytes"):
        return None
    kernel_s = sum(t for name, t in ctx.trace.op_s.items()
                   if KERNEL.match(name))
    if kernel_s <= 0:
        return None
    least, bound = counts.least_time(ctx.counters["kernel_bytes"],
                                     ctx.counters["kernel_ops"], ctx.peaks)
    ctx.counters["kernel_bound"] = bound
    ctx.counters["kernel_s"] = kernel_s
    return 100.0 * least / kernel_s
