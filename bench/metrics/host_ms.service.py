"""host_ms.service: the service's own stage and dispatch seconds (the
``service_flush_stage_seconds`` and ``service_flush_dispatch_seconds``
histograms of ``repro.obs``) over the traced window, mean per flush, in
milliseconds."""


def read(ctx):
    c = ctx.counters
    if not c.get("flushes") or "stage_s" not in c:
        return None
    return 1e3 * (c["stage_s"] + c["dispatch_s"]) / c["flushes"]
