"""mfu.engine: the useful FLOPs of the traced window over its seconds and
the chips' bf16 peak, in percent.

Useful FLOPs are the real participants' local SGD (forward and backward)
and the evaluations' forward passes, counted from the CNN's shapes by
``bench/counts.py``; padded participant slots do not count. The seconds
are the harness's clock around the window's chunks.
"""


def read(ctx):
    c = ctx.counters
    if not c.get("useful_flops") or not c.get("window_s"):
        return None
    peak = ctx.peaks["bf16_flops_per_s"] * ctx.cell["chips"]
    return 100.0 * c["useful_flops"] / (c["window_s"] * peak)
