"""batched_kernel_roofline: the fused decision kernel's share of its
roofline over the traced window, in percent.

The least time is the larger of the real lanes' bytes over the HBM peak
and their operations over the compute peak (``bench/counts.py``; the
lanes and tenant rows of every ``proposed`` request served in the
window). The kernel's time is the device time of its operations in the
trace: the Mosaic custom calls, the only Pallas kernels the service runs.
``counters["kernel_bound"]`` records which bound applies.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import counts  # noqa: E402

KERNEL = r'custom_call_target="tpu_custom_call"'


def read(ctx):
    if ctx.trace is None or not ctx.counters.get("kernel_bytes"):
        return None
    kernel_s = ctx.trace.kernel_s(KERNEL)
    if kernel_s <= 0:
        return None
    least, bound = counts.least_time(ctx.counters["kernel_bytes"],
                                     ctx.counters["kernel_ops"], ctx.peaks)
    ctx.counters["kernel_bound"] = bound
    ctx.counters["kernel_s"] = kernel_s
    return 100.0 * least / kernel_s
