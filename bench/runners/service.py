"""Runner of multi-tenant service configurations (``"runner": "service"``).

The timed path is ``SchedulerService.submit`` and ``flush``: one caller
submits a flush's requests, flushes and waits for the decisions in host
memory before it sends the next flush (closed loop). A flush is timed
from its first ``submit()`` to the returned decisions.

Set-up registers the configuration's tenants, makes every tenant's
request payloads and the flush schedule from the seed, warms the batch
shapes the traffic uses (``warmup_max_batch``; with 0, the first set-up
flushes compile the shapes of a full flush) and serves ``setup_flushes``
flushes of the schedule. After the window, every decision of every
tenant (set-up and window) is compared with the plain reference's
decision at the queues that the tenant's served decisions before it lead
to, a block of requests at a time, and its final queues with Eq. 9
applied to all of them (``checks.service_gaps``).
"""

from __future__ import annotations

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import checks  # noqa: E402
import counts  # noqa: E402
import generator  # noqa: E402
from harness import now, spans  # noqa: E402

SCHEDULE_LEN = 1 << 15   # flushes drawn ahead; the schedule repeats after
BLOCK = 256              # requests per tenant replayed at a time


class Run:
    def __init__(self, cfg: dict, traffic: dict, seed: int, ref,
                 tracing: bool = False):
        self.cfg, self.traffic, self.seed, self.ref = cfg, traffic, seed, ref
        self.span = spans(tracing)
        # the traced run turns the service's own telemetry on for the
        # per-layer readers; timed runs keep the users' default, off
        self.telemetry = tracing
        if traffic["kind"] != "tenant_flushes":
            raise ValueError(f"this runner reads tenant_flushes traffic, not "
                             f"{traffic['kind']!r}")
        self.attempted = 0
        self.failed = 0

    # ------------------------------------------------------------ set-up
    def setup(self) -> None:
        from repro.core import ChannelConfig, SchedulerConfig
        from repro.service import SchedulerService

        cfg, tr = self.cfg, self.traffic
        rng = np.random.default_rng(self.seed)
        self.draws = generator.make_tenants(cfg)
        self.gains, self.raws = generator.make_payloads(
            cfg, self.draws, tr["payloads_per_tenant"], rng)
        self.full = tr["flush_size"] == "all"
        self.schedule = generator.flush_schedule(
            len(self.draws), tr["flush_size"],
            1 if self.full else SCHEDULE_LEN, rng)
        svc = SchedulerService(solver=cfg["solver"], log_requests=False,
                               telemetry=self.telemetry)
        for d in self.draws:
            svc.add_tenant(
                d.name,
                SchedulerConfig(n_clients=d.n, model_bits=d.ell, lam=d.lam,
                                V=d.V, q_floor=cfg["q_floor"],
                                guarantee_one=cfg["guarantee_one"]),
                ChannelConfig(n_clients=d.n,
                              bandwidth_hz=cfg["bandwidth_hz"],
                              noise_power=cfg["noise_power"],
                              p_max=d.p_max, p_bar=cfg["p_bar"],
                              max_spectral_eff=cfg["max_spectral_eff"],
                              min_spectral_eff=cfg["min_spectral_eff"]),
                policy=d.policy)
        self.svc = svc
        self.names = [d.name for d in self.draws]
        self.sent = np.zeros(len(self.draws), np.int64)   # requests made
        self.got = [[] for _ in self.draws]               # their decisions
        self.flushes = 0
        if tr["warmup_max_batch"]:
            svc.warmup(max_batch=tr["warmup_max_batch"])
        for _ in range(tr["setup_flushes"]):
            self._flush()

    def _flush(self):
        """One closed-loop flush of the schedule -> (latency, submit s)."""
        ids = self.schedule[self.flushes % len(self.schedule)]
        svc, names, sent = self.svc, self.names, self.sent
        gains, raws, r = self.gains, self.raws, self.traffic[
            "payloads_per_tenant"]
        span = self.span
        t0 = now()
        with span("submit"):
            for i in ids:
                k = sent[i] % r
                sent[i] += 1
                svc.submit(names[i], gains[i][k], raw=raws[i][k])
        t1 = now()
        with span("flush"):
            out = svc.flush()
        t2 = now()
        self.flushes += 1
        got = self.got
        for i in ids:
            got[i].append(out.get(names[i]))
        return t2 - t0, t1 - t0, len(ids), len(out)

    # ------------------------------------------------------------ window
    def window(self, seconds: float) -> None:
        flush = self._flush
        lat, sub = [], 0.0
        decisions = asked = 0
        self.first_flush = self.flushes
        self.obs0 = self._obs_totals()
        t0 = now()
        while True:
            dt, ds, n_ask, n_got = flush()
            lat.append(dt)
            sub += ds
            asked += n_ask
            decisions += n_got
            if now() - t0 >= seconds:
                break
        self.elapsed = now() - t0
        self.latency = np.asarray(lat)
        self.submit_s = sub
        self.attempted, self.decisions = asked, decisions
        self.failed = asked - decisions

    def _obs_totals(self) -> dict:
        """The service's own flush-segment seconds so far (telemetry on)."""
        obs = self.svc.obs
        if not obs.enabled:
            return {}
        return {"stage_s": obs.stage_s.total,
                "dispatch_s": obs.dispatch_s.total,
                "pull_s": obs.pull_s.total}

    def end_to_end(self) -> dict:
        return {"decisions_per_s": self.decisions / self.elapsed,
                "flush_p95_ms": float(np.percentile(self.latency, 95)) * 1e3}

    def counters(self) -> dict:
        """Counts of the window's work for the per-layer readers."""
        n_flush = len(self.latency)
        lanes = rows = 0
        for f in range(self.first_flush, self.first_flush + n_flush):
            for i in self.schedule[f % len(self.schedule)]:
                if self.draws[i].policy == "proposed":
                    lanes += self.draws[i].n
                    rows += 1
        nbytes, ops = counts.decision_work(lanes, rows)
        out = dict(window_s=self.elapsed, flushes=n_flush,
                   submit_s=self.submit_s, kernel_lanes=lanes,
                   kernel_rows=rows, kernel_bytes=nbytes, kernel_ops=ops)
        out.update({k: v - self.obs0[k]
                    for k, v in self._obs_totals().items()})
        return out

    def release(self) -> None:
        self.z_final = [np.asarray(self.svc.tenant_state(name).z)[:d.n]
                        for name, d in zip(self.names, self.draws)]
        del self.svc

    # ------------------------------------------------------------ check
    def _groups(self):
        groups = {}
        for i, d in enumerate(self.draws):
            groups.setdefault(d.n, []).append(i)
        return groups.values()

    def _seqs(self, ids, lo, hi):
        r = self.traffic["payloads_per_tenant"]
        return [[(self.gains[i][k % r], self.raws[i][k % r])
                 for k in range(lo, min(hi, self.sent[i]))] for i in ids]

    def _program(self, ids, lo, s_max, n) -> dict:
        t = len(ids)
        prog = dict(sel=np.zeros((t, s_max, n), bool),
                    q=np.zeros((t, s_max, n), np.float32),
                    p=np.zeros((t, s_max, n), np.float32),
                    t_comm=np.zeros((t, s_max), np.float32),
                    power=np.zeros((t, s_max), np.float32),
                    served=np.zeros((t, s_max), bool))
        for a, i in enumerate(ids):
            for s, d in enumerate(self.got[i][lo:lo + s_max]):
                if d is None or np.shape(d.q) != (n,):
                    continue
                prog["served"][a, s] = True
                for k in ("sel", "q", "p", "t_comm", "power"):
                    prog[k][a, s] = getattr(d, k)
        return prog

    def compare(self, control: bool = False) -> dict:
        """Every decision against the reference, a block of requests at a
        time: the reference decides each request at the queues that the
        program's served decisions before it lead to by Eq. 9
        (``follow``), so each decision is judged at the state the program
        made it in, and the program's final queues are held to Eq. 9
        applied to all of its decisions. With ``control`` the reference
        in bfloat16, run on its own from empty queues, stands in the
        program's place."""
        import ml_dtypes
        worst = {}
        for ids in self._groups():
            draws = [self.draws[i] for i in ids]
            n = draws[0].n
            s_all = int(max(self.sent[i] for i in ids))
            z_ref = z_low = None
            for lo in range(0, s_all, BLOCK):
                seqs = self._seqs(ids, lo, lo + BLOCK)
                last = lo + BLOCK >= s_all
                if control:
                    low = self.ref.replay(self.cfg, draws, seqs, z0=z_low,
                                          dtype=ml_dtypes.bfloat16)
                    z_low = low["z_dtype"]
                    prog = {k: low[k].astype(np.float32) for k in
                            ("q", "p", "t_comm", "power")}
                    prog.update(sel=low["sel"], served=low["steps"].copy(),
                                z=low["z"])
                else:
                    prog = self._program(ids, lo, max(map(len, seqs)), n)
                    if last:
                        prog["z"] = np.stack([self.z_final[i] for i in ids])
                ref = self.ref.follow(self.cfg, draws, seqs, prog["q"],
                                      prog["p"], prog["served"], z0=z_ref)
                z_ref = ref["z"]
                if not last:
                    prog["z"] = ref["z"]   # queues are compared at the end
                gaps = checks.service_gaps(prog, ref, ref["objective"])
                for k, v in gaps.items():
                    worst[k] = max(worst.get(k, v), v)
        return worst
