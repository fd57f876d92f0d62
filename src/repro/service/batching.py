"""Continuous batching + the multi-tenant ``SchedulerService`` facade.

Requests carry instantaneous gains (the paper's only per-round input) and
the policy's raw selection draws. ``submit()`` ADMITS a request straight
into its bucket's pre-allocated staging arena — one ``np.ndarray`` slot
write per request, no per-request ``np.full`` allocation — and assigns it
to a *wave* (a wave touches each tenant at most once, so state updates
never race; a tenant submitted k times spans k waves). ``flush()`` then
serves one *group* per (wave, bucket): each group is one ``jit(vmap)``
bucket step (``repro/service/step.py``) over the arena's padded batch —
donated state, no per-tenant dispatch. Groups are dispatched back to
back WITHOUT pulling results (JAX async dispatch), so host-side staging
and dispatch of group k overlap device compute of group k-1; results are
pulled once, after every group is in flight.

The batch row axis pads with sentinel rows (row index = T): the gather
clamps them onto an arbitrary real tenant's inputs (garbage compute,
discarded) and the scatter drops their state writes — pad rows can never
alter a real tenant's bits, which the padding-hygiene test pins. The
staged path builds bit-identical batch arrays to the legacy
pad-per-request path (``staging=False``, kept as the parity reference),
so both run the same compiled programs on the same inputs
(tests/test_service.py).

Replay-log failure atomicity: each group is appended to the
:class:`~repro.service.replay.RequestLog` immediately after its state
scatter is dispatched. A ``flush()`` that raises partway therefore leaves
the log holding exactly the groups whose queue updates happened — replay
from the last snapshot reproduces the live state bit for bit even across
the failure (the remaining queued requests are dropped). Replaying a log
from the starting snapshot reproduces every response bit for bit (the
service is deterministic: all randomness arrives with the requests).

Tenant lifecycle: ``evict(name)`` spills a tenant's padded state row
through the checkpoint substrate (``spill_dir``; in-memory otherwise)
and compacts its bucket; ``reload(name)`` — or a ``submit`` to a spilled
tenant — re-admits it with bitwise-identical queues. ``evict_lru()``
picks the least-recently-used resident. ``compact_log()`` snapshots
state and drops the served log entries, bounding host memory while
keeping replay bit-exact.

Telemetry (``repro.obs``, off by default): the service records flush
latency split into its three host segments (arena staging / async
dispatch / result pull), per-bucket group occupancy and pad waste, queue
depth, per-decision comm time, tenant lifecycle counters, replay-log
growth, and — keyed by ``step_signature`` — every jit-cache miss the
serving path pays (the PR-8 silent-recompile pathology, made visible;
``warmup()`` seeds the tracker so warm hits are counted too). All
recording is host-side, outside jit, which keeps telemetry-on serving
and replay bitwise-identical to telemetry-off (tests/test_obs.py).
``metrics_snapshot()`` exports dict / JSON / Prometheus text.

Program spans (``repro.obs.span``, on the profiler's clock, recorded with
telemetry on or off): ``service.submit`` per request; per flush the root
``service.flush``, and inside it per serve group ``service.stage``,
``service.dispatch``, one ``service.pull`` per device-to-host transfer
(six a group) and ``service.unpack``, and ``service.log`` around each
replay-log append. Every span of one flush carries its ordinal
(``flush=<n>``); a request's ``service.submit`` carries the ordinal of
the flush that will serve it.
"""

from __future__ import annotations

import json
import os
import re
import warnings
from typing import Dict, List, NamedTuple, Optional, Union

import jax
import numpy as np

from repro.checkpoint.io import load_pytree, save_pytree
from repro.core.channel import ChannelConfig
from repro.core.policies import POLICY_DRAWS, PolicyState
from repro.core.scheduler import SchedulerConfig
from repro.fl.client_shard import POLICY_RAW_PAD
from repro.obs import metrics as obs_metrics
from repro.obs.export import EventLog, json_snapshot, prometheus_text
from repro.obs.instrument import ServiceInstruments, perf
from repro.obs.profile import span
from repro.service.replay import LoggedRequest, RequestLog
from repro.service.state import (BucketKey, TenantSpec, TenantStore,
                                 bucket_width)
from repro.service.step import make_bucket_step, step_signature

GAINS_PAD = 0.0  # below every clipped channel gain (gain_bounds lo > 0)


class Decision(NamedTuple):
    """One served scheduling decision (host arrays, tenant's real N)."""

    sel: np.ndarray      # (N,) bool participation indicators
    q: np.ndarray        # (N,) f32 selection probabilities
    p: np.ndarray        # (N,) f32 transmit powers
    t_comm: np.float32   # TDMA round communication time (Eq. 8 sum)
    power: np.float32    # sum_n P_n q_n this round
    n_sel: np.int64      # participants this round


class _Pending(NamedTuple):
    tenant: str
    gains: np.ndarray
    raw: object


class _RawProto(NamedTuple):
    """One policy's raw-draw layout: treedef + per-leaf kind/dtype/fill."""

    treedef: object
    scalar: tuple      # per leaf: True if a per-request scalar (no lane axis)
    dtypes: tuple
    fills: tuple       # per-lane pad fill per leaf (POLICY_RAW_PAD)


def _next_pow2(n: int) -> int:
    return 1 << max(0, int(n) - 1).bit_length()


def _pull(x, fl: int) -> np.ndarray:
    """One blocking device-to-host transfer of flush ``fl``, in a span of
    its own (the count of ``service.pull`` spans is the count of
    transfers)."""
    with span("service.pull", flush=fl):
        return np.asarray(x)


def _pad_lane(x: np.ndarray, width: int, fill) -> np.ndarray:
    out = np.full((width,), fill, x.dtype)
    out[: x.shape[0]] = x
    return out


class _Stage:
    """Pre-allocated staging arenas for one bucket within one wave.

    Admission writes each request into arena slot ``count`` (a slice
    write into pinned host buffers — the per-request cost the old
    pad-per-flush path paid as fresh ``np.full`` allocations + stacks);
    dispatch takes one bulk copy of the active ``[:b_pad]`` slice (so
    arena reuse can never alias an in-flight async computation). Arenas
    grow by doubling and are pooled per bucket across flushes.
    """

    def __init__(self, bkey: BucketKey, proto: _RawProto, cap: int = 8):
        self.bkey = bkey
        self.proto = proto
        self.cap = 0
        self.count = 0
        self.rows: Optional[np.ndarray] = None
        self.gains: Optional[np.ndarray] = None
        self.raw: List[np.ndarray] = []
        self._grow(cap)

    def _grow(self, cap: int) -> None:
        nb = self.bkey.n_bucket

        def bigger(old, shape, dtype):
            new = np.zeros(shape, dtype)
            if old is not None:
                new[: old.shape[0]] = old
            return new

        self.rows = bigger(self.rows, (cap,), np.int32)
        self.gains = bigger(self.gains, (cap, nb), np.float32)
        old = self.raw or [None] * len(self.proto.scalar)
        self.raw = [bigger(a, (cap,) if s else (cap, nb), d)
                    for a, s, d in zip(old, self.proto.scalar,
                                       self.proto.dtypes)]
        self.cap = cap

    def put(self, n: int, gains: np.ndarray, raw_leaves) -> None:
        """Admit one request: slot writes only, no allocation."""
        if self.count == self.cap:
            self._grow(self.cap * 2)
        i = self.count
        g = self.gains[i]
        g[:n] = gains
        g[n:] = GAINS_PAD
        for arena, leaf, scalar, fill in zip(self.raw, raw_leaves,
                                             self.proto.scalar,
                                             self.proto.fills):
            if scalar:
                arena[i] = leaf
            else:
                a = arena[i]
                a[:n] = leaf
                a[n:] = fill
        self.count += 1

    def batch(self, rows: List[int], sentinel: int, b_pad: int):
        """The padded (rows, gains, raw) batch for dispatch (bulk copies
        of the active slice; sentinel slots zeroed — their payloads are
        discarded anyway, but zeros keep them finite and reproducible)."""
        c = self.count
        if b_pad > self.cap:
            self._grow(b_pad)
        self.rows[:c] = rows
        self.rows[c:b_pad] = sentinel
        self.gains[c:b_pad] = 0.0
        for arena in self.raw:
            arena[c:b_pad] = 0
        return (self.rows[:b_pad].copy(), self.gains[:b_pad].copy(),
                jax.tree.unflatten(self.proto.treedef,
                                   [a[:b_pad].copy() for a in self.raw]))

    def reset(self) -> None:
        self.count = 0


class _Wave:
    """One serving wave: each tenant at most once, grouped per bucket."""

    __slots__ = ("seen", "groups", "stages")

    def __init__(self):
        self.seen: set = set()
        self.groups: Dict[BucketKey, List[_Pending]] = {}
        self.stages: Dict[BucketKey, _Stage] = {}


class SchedulerService:
    """Online multi-tenant Theorem-2 scheduling service.

    >>> svc = SchedulerService()
    >>> svc.add_tenant("cityA", scfg, ch)                 # Algorithm 2
    >>> svc.submit("cityA", gains, key=k)                 # one round's CSI
    >>> decision = svc.flush()["cityA"]                   # (sel, q, p) + accounting

    ``solver="pallas"`` swaps the Theorem-2 solve for the tiled Pallas
    kernel (``repro.kernels.scheduler_solve``); each bucket must then be
    configuration-homogeneous (kernel parameters are compile-time static)
    and the bitwise-parity contract relaxes to the kernel's float32
    round-off. The default ``"jnp"`` path serves heterogeneous tenants
    from one compiled program per bucket and is bitwise-equal to
    ``run_simulation_scan``'s decisions (tests/test_service.py).

    ``solver="pallas_fused"`` serves ``proposed`` buckets through the
    bucket-batched fused decision megakernel
    (``kernels/decision_fused.py``): every scalar is a runtime operand
    row, so — unlike ``"pallas"`` — heterogeneous tenants still batch in
    one program AND the full bitwise contract holds. Non-``proposed``
    buckets fall back to the stitched jnp rows (identical results).
    """

    def __init__(self, solver: str = "jnp", log_requests: bool = True,
                 staging: bool = True, spill_dir: Optional[str] = None,
                 telemetry: Optional[bool] = None,
                 event_log: Union[None, str, EventLog] = None,
                 log_warn_bytes: float = float(1 << 28)):
        """``log_requests=False`` disables the replay log entirely;
        deployments that keep it should call :meth:`compact_log` on their
        checkpoint cadence — compaction records the snapshot in the log,
        so replay stays bit-exact while host memory stays bounded.

        ``staging=False`` falls back to the legacy pad-per-request batch
        build (one ``np.full`` + stack per request) — kept as the bitwise
        parity reference for the staged arenas, not for production use.

        ``spill_dir`` routes :meth:`evict` state spills through the
        checkpoint substrate on disk; by default spilled rows stay on the
        host heap.

        ``telemetry`` turns this service's metrics registry on/off
        (``None`` inherits the process-wide ``repro.obs.configure``
        switch, which starts off). All recording is host-side and outside
        jit: served decisions, queue updates, and replay are
        BITWISE-IDENTICAL with telemetry on or off (tests/test_obs.py);
        off, the hot path pays one attribute load + no-op call per site.
        Read metrics via :meth:`metrics_snapshot`.

        ``event_log`` — an optional JSONL path (or shared
        :class:`~repro.obs.export.EventLog`) for lifecycle events (admit
        / evict / reload / compact / warmup / log-growth warnings). The
        in-memory event tail is always kept; file writes are rank-0
        gated.

        ``log_warn_bytes`` — estimated retained replay-log bytes above
        which the service warns (once) that the unbounded-by-design log
        wants a :meth:`compact_log` cadence. Default 256 MiB."""
        if solver not in ("jnp", "pallas", "pallas_fused"):
            raise ValueError(f"unknown solver {solver!r} "
                             "(want 'jnp'|'pallas'|'pallas_fused')")
        self.solver = solver
        self.log_requests = log_requests
        self.staging = staging
        self.spill_dir = spill_dir
        self.obs = ServiceInstruments(obs_metrics.new_registry(telemetry))
        self.events = (event_log if isinstance(event_log, EventLog)
                       else EventLog(event_log))
        self.log_warn_bytes = float(log_warn_bytes)
        self.store = TenantStore()
        self.store.obs = self.obs
        self.log = RequestLog()
        self._waves: List[_Wave] = []
        self._steps: Dict[BucketKey, object] = {}
        self._pool: Dict[BucketKey, List[_Stage]] = {}
        self._protos: Dict[str, _RawProto] = {}
        self._spilled: Dict[str, tuple] = {}   # name -> (spec, row | path)
        self._spill_seq = 0
        self._tick = 0
        self._last_used: Dict[str, int] = {}
        self._bstrs: Dict[BucketKey, str] = {}   # cached as_string() forms
        self._flush_seq = 0   # ordinal of the next flush (span metadata)

    # ------------------------------------------------------------ tenants
    def add_tenant(self, name: str, scfg: SchedulerConfig,
                   ch: ChannelConfig, policy: str = "proposed",
                   m_avg: float = 0.0) -> TenantSpec:
        if name in self._spilled:
            raise ValueError(f"tenant {name!r} is evicted (spilled); "
                             "reload() it instead of re-registering")
        spec = self.store.add(TenantSpec(name=name, scfg=scfg, ch=ch,
                                         policy=policy, m_avg=m_avg))
        self._invalidate_step(spec.bucket)
        self._touch(name)
        self.events.emit("admit", tenant=name,
                         bucket=self._bucket_str(spec.bucket))
        return spec

    def _bucket_str(self, bkey: BucketKey) -> str:
        """Cached ``bkey.as_string()`` (metric labels, events) — the flush
        path does a dict lookup instead of re-formatting per group."""
        s = self._bstrs.get(bkey)
        if s is None:
            s = self._bstrs[bkey] = bkey.as_string()
        return s

    def _invalidate_step(self, bkey: BucketKey) -> None:
        """Drop a bucket's cached step if tenant-set changes can affect
        it. Only ``solver='pallas'`` bakes the tenant set into the step
        (its solve_fn is built against the bucket's configuration
        homogeneity); the jnp/fused steps take every per-tenant quantity
        as runtime operands, so the SAME jit function serves any tenant
        count — keeping it preserves the compiled (T, batch)-shape
        variants across evict/reload churn and across admissions."""
        if self.solver == "pallas":
            self._steps.pop(bkey, None)
            # the new step instance has a fresh jit cache — drop the
            # host-side mirror too, so re-dispatched shapes count as the
            # fresh compiles they are
            self.obs.compiles.forget(bkey)

    def raw_structure(self, name: str):
        """An example raw-draw pytree for this tenant (log loading)."""
        spec = self.store.spec(name)
        return POLICY_DRAWS[spec.policy](jax.random.PRNGKey(0), spec.n)

    def _proto(self, policy: str) -> _RawProto:
        if policy not in self._protos:
            example = POLICY_DRAWS[policy](jax.random.PRNGKey(0), 4)
            leaves, treedef = jax.tree.flatten(example)
            fills = treedef.flatten_up_to(POLICY_RAW_PAD[policy])
            self._protos[policy] = _RawProto(
                treedef=treedef,
                scalar=tuple(np.ndim(x) == 0 for x in leaves),
                dtypes=tuple(np.asarray(x).dtype for x in leaves),
                fills=tuple(fills))
        return self._protos[policy]

    def _touch(self, name: str) -> None:
        self._last_used[name] = self._tick
        self._tick += 1

    # ------------------------------------------------------------ serving
    def submit(self, name: str, gains, raw=None, key=None) -> None:
        """Queue one round's scheduling request for a tenant.

        ``gains`` are the tenant's instantaneous channel gains (finite
        and positive, shape (N,)). Exactly one of ``raw`` (the policy's
        pre-drawn raw selection draws, ``POLICY_DRAWS`` layout) or ``key``
        (a PRNG key the service draws them from — the same split the
        engines use) must be given. Submitting to an evicted tenant
        reloads it first.
        """
        with span("service.submit", flush=self._flush_seq):
            self._submit(name, gains, raw, key)

    def _submit(self, name: str, gains, raw, key) -> None:
        if name in self._spilled:
            self.reload(name)
        spec = self.store.spec(name)
        gains = np.asarray(gains, np.float32)
        if gains.shape != (spec.n,):
            raise ValueError(f"tenant {name!r} expects gains of shape "
                             f"({spec.n},), got {gains.shape}")
        if not np.all(np.isfinite(gains)) or not np.all(gains > 0.0):
            # every channel model emits gains clipped into a finite
            # positive band (gain_bounds); non-positive gains would tie
            # greedy's threshold with the 0.0 pad fill (pad lanes
            # selected) and divide by zero in the Theorem-2 solve, while
            # +inf poisons the solve's log2 SNR and NaN-contaminates the
            # shared bucket batch
            raise ValueError(f"tenant {name!r} gains must be finite and "
                             "positive (channel gains are clipped into a "
                             "finite band above 0)")
        if (raw is None) == (key is None):
            raise ValueError("pass exactly one of raw= or key=")
        if raw is None:
            raw = POLICY_DRAWS[spec.policy](key, spec.n)
        raw = jax.tree.map(np.asarray, raw)
        proto = self._proto(spec.policy)
        if jax.tree.structure(raw) != proto.treedef:
            raise ValueError(
                f"tenant {name!r} raw draws do not match the "
                f"{spec.policy!r} POLICY_DRAWS layout")
        bkey = spec.bucket
        wave = next((w for w in self._waves if name not in w.seen), None)
        if wave is None:
            wave = _Wave()
            self._waves.append(wave)
        wave.seen.add(name)
        wave.groups.setdefault(bkey, []).append(_Pending(name, gains, raw))
        if self.staging:
            stage = wave.stages.get(bkey)
            if stage is None:
                pool = self._pool.get(bkey)
                stage = pool.pop() if pool else _Stage(bkey, proto)
                wave.stages[bkey] = stage
            stage.put(spec.n, gains, jax.tree.leaves(raw))
        self._touch(name)
        self.obs.submits.inc()

    @property
    def n_queued(self) -> int:
        return sum(len(g) for w in self._waves for g in w.groups.values())

    def flush(self, log: bool = True) -> Dict[str, Decision]:
        """Serve every queued request; return ``{tenant: Decision}``.

        A tenant submitted k times is served k times, in order (k waves);
        the returned dict carries its LAST decision. Serve groups — one
        bucket's batch within one wave — are dispatched without pulling
        results, so staging/dispatch of group k overlaps device compute
        of group k-1; each group is appended to the replay log right
        after its dispatch, which makes the log FAILURE-ATOMIC: a flush
        that raises partway has logged exactly the groups whose queue
        updates happened (the not-yet-served requests are dropped), so
        replay from the last snapshot reproduces the live state bit for
        bit even across the failure.
        """
        fl = self._flush_seq
        self._flush_seq += 1
        with span("service.flush", flush=fl):
            return self._flush(log, fl)

    def _flush(self, log: bool, fl: int) -> Dict[str, Decision]:
        obs = self.obs
        t_start = perf()
        if obs.enabled:
            obs.queue_depth.set(self.n_queued)
        waves, self._waves = self._waves, []
        pending = []
        try:
            for w in waves:
                for bkey, reqs in w.groups.items():
                    outs = self._dispatch_group(bkey, reqs,
                                                w.stages.get(bkey), fl)
                    if log and self.log_requests:
                        with span("service.log", flush=fl):
                            self.log.append_entry(
                                [LoggedRequest(*r) for r in reqs])
                    pending.append((reqs, outs))
        finally:
            for w in waves:
                for bkey, stage in w.stages.items():
                    stage.reset()
                    self._pool.setdefault(bkey, []).append(stage)
        t_pull = perf()
        responses: Dict[str, Decision] = {}
        rec_t_comm = obs.t_comm.record if obs.enabled else None
        for reqs, outs in pending:
            sel, q, p, t_comm, power, n_sel = [_pull(x, fl) for x in outs]
            with span("service.unpack", flush=fl):
                for i, r in enumerate(reqs):
                    n = self.store.spec(r.tenant).n
                    responses[r.tenant] = Decision(
                        sel=sel[i, :n], q=q[i, :n], p=p[i, :n],
                        t_comm=t_comm[i], power=power[i],
                        n_sel=np.int64(n_sel[i]))
                    if rec_t_comm is not None:
                        rec_t_comm(float(t_comm[i]))
        t_end = perf()
        obs.pull_s.record(t_end - t_pull)
        obs.flush_s.record(t_end - t_start)
        obs.flushes.inc()
        if log and self.log_requests:
            self._log_health()
        return responses

    def _log_health(self) -> None:
        """Replay-log growth gauges + the one-time threshold warning.

        The log is unbounded BY DESIGN (it is the replay trajectory);
        this surfaces that instead of footnoting it — when the estimated
        retained bytes cross ``log_warn_bytes`` the service emits one
        ``log_growth_warning`` event and one Python warning nudging the
        :meth:`compact_log` cadence."""
        est = self.log.bytes_est
        self.obs.log_entries.set(len(self.log))
        self.obs.log_bytes.set(est)
        if est > self.log_warn_bytes:
            rec = self.events.once(
                "log_growth", "log_growth_warning",
                entries=len(self.log), bytes_est=est,
                threshold=self.log_warn_bytes)
            if rec is not None:
                warnings.warn(
                    f"replay log holds ~{est / 2**20:.0f} MiB across "
                    f"{len(self.log)} entries (threshold "
                    f"{self.log_warn_bytes / 2**20:.0f} MiB); it grows "
                    "unbounded by design — call compact_log() on your "
                    "checkpoint cadence to bound host memory",
                    RuntimeWarning, stacklevel=3)

    def warmup(self, max_batch: int = 8) -> None:
        """Pre-compile every bucket's step for all power-of-two batch
        shapes up to ``max_batch`` by serving all-sentinel batches (the
        scatter drops every row, so tenant state is bitwise-untouched).
        Moves the compile spikes out of the serving path: small-flush p99
        becomes steady-state instead of a first-shape compilation."""
        obs = self.obs
        n_warmed = 0
        for bkey, bucket in self.store.buckets().items():
            step = self._bucket_step(bkey, bucket)
            proto = self._proto(bkey.policy)
            bstr = self._bucket_str(bkey)
            b = 1
            while b <= _next_pow2(max_batch):
                rows = np.full((b,), bucket.size, np.int32)
                gains = np.zeros((b, bkey.n_bucket), np.float32)
                raw = jax.tree.unflatten(proto.treedef, [
                    np.zeros((b,) if s else (b, bkey.n_bucket), d)
                    for s, d in zip(proto.scalar, proto.dtypes)])
                fresh = obs.compiles.warm(
                    step_signature(bkey, bucket.size, b, self.solver),
                    bucket=bstr, batch=b, solver=self.solver)
                t0 = perf()
                out = step(bucket.state, bucket.coeffs, bucket.acct,
                           bucket.n_real, rows, gains, raw)
                if fresh:
                    # jit traces + compiles synchronously at call time
                    # (only execution is async), so the first call's wall
                    # is trace + compile + dispatch
                    obs.compiles.compile_s.inc(perf() - t0)
                    n_warmed += 1
                bucket.state = out[-1]
                b *= 2
            jax.block_until_ready(bucket.state.z)
        self.events.emit("warmup", shapes_compiled=n_warmed,
                         max_batch=max_batch)

    def _bucket_step(self, bkey: BucketKey, bucket):
        if bkey not in self._steps:
            solve_fn = None
            if self.solver == "pallas":
                solve_fn = self._pallas_solve(bkey, bucket)
            fused = (self.solver == "pallas_fused"
                     and bkey.policy == "proposed")
            self._steps[bkey] = make_bucket_step(
                bkey.policy, bkey.n_bucket, bkey.acct_len,
                bkey.guarantee_one, solve_fn=solve_fn, fused=fused)
        return self._steps[bkey]

    def _pallas_solve(self, bkey: BucketKey, bucket):
        from repro.fl.engine import make_solve_fn

        configs = {(s.scfg, s.ch) for s in bucket.tenants}
        if len(configs) > 1:
            raise ValueError(
                f"solver='pallas' needs bucket {bkey.as_string()!r} to be "
                "configuration-homogeneous (kernel parameters are "
                f"compile-time static); it mixes {len(configs)} configs")
        scfg, ch = next(iter(configs))
        return make_solve_fn(scfg, ch, "pallas",
                             block=min(1024, bkey.n_bucket))

    def _dispatch_group(self, bkey: BucketKey, reqs: List[_Pending],
                        stage: Optional[_Stage], fl: int):
        """Dispatch one (wave, bucket) group of flush ``fl``; returns
        device outputs WITHOUT pulling them (async — the next group's host
        staging overlaps this group's device compute)."""
        obs = self.obs
        bucket = self.store.buckets()[bkey]
        step = self._bucket_step(bkey, bucket)
        b_pad = _next_pow2(len(reqs))
        row_ids = [self.store.row(r.tenant) for r in reqs]
        t0 = perf()
        with span("service.stage", flush=fl):
            if stage is not None:
                rows, gains, raw = stage.batch(row_ids, bucket.size, b_pad)
            else:
                rows, gains, raw = self._legacy_batch(bkey, bucket, reqs,
                                                      row_ids, b_pad)
        t1 = perf()
        with span("service.dispatch", flush=fl):
            fresh = obs.compiles.miss(
                step_signature(bkey, bucket.size, b_pad, self.solver),
                bucket=self._bucket_str(bkey), batch=b_pad,
                solver=self.solver)
            sel, q, p, t_comm, power, n_sel, new_state = step(
                bucket.state, bucket.coeffs, bucket.acct, bucket.n_real,
                rows, gains, raw)
        t2 = perf()
        bucket.state = new_state      # old buffers were donated
        obs.stage_s.record(t1 - t0)
        obs.dispatch_s.record(t2 - t1)
        if fresh:
            # first dispatch of a shape traces + compiles synchronously;
            # its wall is the compile spike the serving path just paid
            obs.compiles.compile_s.inc(t2 - t1)
        if obs.enabled:
            occ, waste = obs.bucket(self._bucket_str(bkey))
            occ.record(len(reqs))
            waste.record((b_pad - len(reqs)) / b_pad)
            obs.groups.inc()
            obs.requests.inc(len(reqs))
        return sel, q, p, t_comm, power, n_sel

    def _legacy_batch(self, bkey: BucketKey, bucket, reqs, row_ids,
                      b_pad: int):
        """The PR-5 pad-per-request batch build (one ``np.full`` + tree
        map per request, stacked per flush) — the staged arenas' bitwise
        parity reference (tests/test_service.py)."""
        nb = bkey.n_bucket
        rows = np.full((b_pad,), bucket.size, np.int32)  # pad: dropped
        gains = np.zeros((b_pad, nb), np.float32)
        raw_rows = []
        fills = POLICY_RAW_PAD[bkey.policy]
        for i, r in enumerate(reqs):
            rows[i] = row_ids[i]
            gains[i] = _pad_lane(r.gains, nb, GAINS_PAD)
            raw_rows.append(jax.tree.map(
                lambda x, f: x if np.ndim(x) == 0
                else _pad_lane(np.asarray(x), nb, f), r.raw, fills))
        for _ in range(b_pad - len(reqs)):   # sentinel-row payloads
            raw_rows.append(jax.tree.map(
                lambda x: np.zeros_like(np.asarray(x)), raw_rows[0]))
        raw = jax.tree.map(lambda *xs: np.stack(xs), *raw_rows)
        return rows, gains, raw

    # --------------------------------------------------- tenant lifecycle
    def evict(self, name: str):
        """Spill ``name``'s state row through the checkpoint substrate
        and compact its bucket. The tenant stays known to the service
        (``reload`` or a ``submit`` re-admits it, bitwise); its decisions
        after reload are identical to never having been evicted."""
        for w in self._waves:
            if name in w.seen:
                raise ValueError(f"tenant {name!r} has queued requests; "
                                 "flush() before evicting")
        spec = self.store.spec(name)
        row = self.store.evict(name)
        self._invalidate_step(spec.bucket)
        self._last_used.pop(name, None)
        if self.spill_dir is not None:
            fname = re.sub(r"[^\w.-]", "_", name)
            path = os.path.join(self.spill_dir,
                                f"spill-{self._spill_seq}-{fname}.npz")
            self._spill_seq += 1
            save_pytree(path, row)
            self._spilled[name] = (spec, path)
        else:
            self._spilled[name] = (spec, row)
        self.obs.spills.inc()
        self.obs.spilled.set(len(self._spilled))
        self.events.emit("evict", tenant=name,
                         spill="disk" if self.spill_dir else "heap")
        return row

    def reload(self, name: str) -> TenantSpec:
        """Re-admit an evicted tenant with bitwise-identical queues."""
        if name not in self._spilled:
            raise KeyError(f"tenant {name!r} is not spilled")
        spec, ref = self._spilled.pop(name)
        if isinstance(ref, str):
            nb = bucket_width(spec.n)
            template = PolicyState(
                z=jax.ShapeDtypeStruct((nb,), np.float32),
                aux=jax.ShapeDtypeStruct((nb,), np.float32),
                t=jax.ShapeDtypeStruct((), np.int32))
            row = jax.tree.map(np.asarray, load_pytree(ref, template))
            os.remove(ref)
        else:
            row = ref
        out = self.store.readmit(spec, row)
        self._invalidate_step(spec.bucket)
        self._touch(name)
        self.obs.reloads.inc()
        self.obs.spilled.set(len(self._spilled))
        self.events.emit("reload", tenant=name)
        return out

    def evict_lru(self) -> str:
        """Evict the least-recently-used resident tenant; returns its
        name. Tenants with queued requests are never candidates."""
        staged: set = set()
        for w in self._waves:
            staged |= w.seen
        cands = [n for n in self.store.tenants if n not in staged]
        if not cands:
            raise ValueError("no evictable tenant (none resident, or all "
                             "have queued requests)")
        name = min(cands, key=lambda n: self._last_used.get(n, -1))
        self.evict(name)
        return name

    @property
    def spilled(self) -> tuple:
        """Names of currently-evicted (spilled) tenants."""
        return tuple(self._spilled)

    # --------------------------------------------------- state management
    def tenant_state(self, name: str):
        return self.store.tenant_state(name)

    def snapshot(self):
        return self.store.snapshot()

    def restore(self, snap) -> None:
        self.store.restore(snap)

    def save(self, path: str) -> None:
        self.store.save(path)

    def load(self, path: str) -> None:
        self.store.load(path)

    def compact_log(self):
        """Snapshot the current state and compact the replay log against
        it: served entries are dropped, the snapshot rides in the log,
        and ``log.replay`` of the compacted log bit-exactly reproduces
        what replaying the full log would have (tests/test_service.py).
        Call on the checkpoint cadence to bound host memory. Returns the
        snapshot."""
        if self._waves:
            raise ValueError("flush() before compacting the log "
                             "(queued requests are not yet in it)")
        snap = self.snapshot()
        dropped = self.log.compact(snap)
        self.obs.log_compactions.inc()
        self.obs.log_entries.set(0)
        self.obs.log_bytes.set(0)
        self.events.emit("compact", entries_dropped=dropped)
        return snap

    # --------------------------------------------------------- telemetry
    def metrics_snapshot(self, fmt: str = "dict"):
        """This service's metrics, in one of three formats.

        ``fmt="dict"`` (default) — a JSON-serializable dict: the metric
        list plus on-demand extras (tenant counts, per-bucket Z-queue
        summaries — the paper's Eq. 9 virtual power queues, pulled to the
        host HERE, off the serving path, and only when telemetry is on).
        ``fmt="json"`` — the same, serialized. ``fmt="prometheus"`` —
        the Prometheus text exposition format, ready to serve from a
        ``/metrics`` endpoint. With telemetry off, returns the empty
        registry (and skips the device pulls entirely).
        """
        obs = self.obs
        if obs.enabled:
            obs.queue_depth.set(self.n_queued)
            for bkey, b in self.store.buckets().items():
                bstr = self._bucket_str(bkey)
                z = np.asarray(b.state.z)    # host pull, snapshot-time only
                g = obs.registry.gauge
                g("service_z_mean", bucket=bstr).set(float(z.mean()))
                g("service_z_max", bucket=bstr).set(float(z.max()))
                g("service_bucket_tenants", bucket=bstr).set(b.size)
        if fmt == "prometheus":
            return prometheus_text(obs.registry)
        snap = json_snapshot(
            obs.registry,
            tenants={"resident": len(self.store),
                     "spilled": len(self._spilled)},
            queued=self.n_queued,
            log={"entries": len(self.log), "bytes_est": self.log.bytes_est,
                 "n_compacted": self.log.n_compacted},
            compile_misses=self.obs.compiles.misses_total())
        if fmt == "json":
            return json.dumps(snap)
        if fmt != "dict":
            raise ValueError(f"unknown fmt {fmt!r} "
                             "(want 'dict'|'json'|'prometheus')")
        return snap
