"""idle_share.<cells>: the share of the traced window in which no
operation ran on the device (profiler trace, ``bench/trace_reduce.py``),
in percent. One reader for each split of the quantity (``idle_share.engine``,
``idle_share.service``)."""


def read(ctx):
    if ctx.trace is None or ctx.trace.window_s <= 0:
        return None
    return 100.0 * ctx.trace.idle_share
