"""Runtime telemetry: metrics registry, instrumentation, exporters.

Off-by-default observability for the online control loop the paper
deploys (Eq. 9 queues, per-round comm time, selection counts) and for the
serving machinery around it (flush latency segments, recompile tracking,
tenant lifecycle, replay-log growth). The contract that makes it safe to
thread through every hot path: ALL recording is host-side, outside jit —
telemetry-on runs are bitwise-equal to telemetry-off runs
(tests/test_obs.py). Program spans (:func:`span`, ``obs/profile.py``)
are profiler annotations, recorded whether telemetry is on or off.

Quickstart::

    from repro import obs
    obs.configure(True)                       # process-wide switch
    svc = SchedulerService(telemetry=True)    # or per-service
    ...serve...
    print(svc.metrics_snapshot(fmt="prometheus"))
"""

from repro.obs.export import EventLog, json_snapshot, prometheus_text
from repro.obs.instrument import (CompileTracker, EngineInstruments,
                                  ServiceInstruments, TournamentInstruments,
                                  noop_instruments)
from repro.obs.metrics import (NOOP, Counter, Gauge, Histogram,
                               MetricsRegistry, configure, default_registry,
                               enabled, new_registry)
from repro.obs.profile import span

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "NOOP",
    "configure", "default_registry", "enabled", "new_registry",
    "CompileTracker", "EngineInstruments", "ServiceInstruments",
    "TournamentInstruments", "noop_instruments",
    "EventLog", "json_snapshot", "prometheus_text", "span",
]
