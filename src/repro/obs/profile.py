"""Optional ``jax.profiler`` trace annotations around flush waves.

For the deep dives the metric counters cannot answer ("WHAT inside this
458 ms flush was compile vs dispatch vs device compute"), the service can
annotate each flush wave with a named ``jax.profiler.TraceAnnotation`` so
a captured trace (``jax.profiler.start_trace`` -> TensorBoard) shows the
serve groups as labelled spans.

Annotations cost a call into the profiler even when no trace is being
captured, so :func:`trace_span` is a no-op unless process-wide telemetry
is on (``repro.obs.configure(True)``) — the hot path pays one bool check.
"""

from __future__ import annotations

import contextlib

from jax.profiler import TraceAnnotation

from repro.obs import metrics

_NULL = contextlib.nullcontext()


def trace_span(name: str):
    """Context manager: a named profiler span when telemetry is enabled.

    >>> with trace_span("service.flush/wave0"):
    ...     dispatch_group(...)
    """
    if not metrics.enabled():
        return _NULL
    return TraceAnnotation(name)
