"""pull_ms.service: the service's own result-pull seconds (the
``service_flush_pull_seconds`` histogram of ``repro.obs``) over the traced
window, mean per flush, in milliseconds."""


def read(ctx):
    c = ctx.counters
    if not c.get("flushes") or "pull_s" not in c:
        return None
    return 1e3 * c["pull_s"] / c["flushes"]
