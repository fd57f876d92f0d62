"""submit_ms.service: the harness's clock around one flush's ``submit()``
calls, mean per flush of the traced window, in milliseconds."""


def read(ctx):
    c = ctx.counters
    if not c.get("flushes"):
        return None
    return 1e3 * c["submit_s"] / c["flushes"]
