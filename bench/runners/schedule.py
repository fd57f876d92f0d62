"""Runner of scheduling-only configurations (``"runner": "schedule"``).

The timed path is the stepping scheduling runner
(``repro.fl.client_shard.make_schedule_chunk_runner``): back-to-back
chunks of ``chunk_rounds`` rounds from a carry of
``init_schedule_carry`` (the Eq. 9 queues, the channel state and the run
key, donated from call to call), each chunk's one output array (every
round's Eq. 8 sums, selection count, overflow and selected client ids)
pulled to the host once, in a closed loop: an aggregator that reads each
chunk's participants and costs before it asks for the next. Each chunk's
copy to the host starts at its dispatch.
Set-up builds the runner and its carry from the seed and runs the first
``setup_chunks`` chunks, the first of which compiles; it keeps the first
chunk's rows and the queues it ended at.

The comparison (``<config>_ref.py``'s ``judge``) follows every lane of
that first chunk from empty queues, as the module docstring of the
reference sets out; it also holds the run key after the window to the
key chain (every round splits it once, so a chunk that left its carry
unchanged shows) and counts the selected clients that did not fit in the
id slots over every round run.
"""

from __future__ import annotations

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import counts  # noqa: E402
from harness import now, seed_key, spans  # noqa: E402


class Run:
    def __init__(self, cfg: dict, traffic: dict, seed: int, ref,
                 tracing: bool = False):
        self.cfg, self.traffic, self.seed, self.ref = cfg, traffic, seed, ref
        self.span = spans(tracing)
        if traffic["kind"] != "schedule_chunks":
            raise ValueError(f"this runner reads schedule_chunks traffic, "
                             f"not {traffic['kind']!r}")
        self.rounds = traffic["chunk_rounds"]
        self.attempted = 0
        self.failed = 0
        self.overflow = 0       # over every round run
        self.max_n_sel = 0

    def _take(self, rows: dict) -> dict:
        self.overflow += int(rows["overflow"].sum())
        self.max_n_sel = max(self.max_n_sel, int(rows["n_sel"].max()))
        return rows

    # ------------------------------------------------------------ set-up
    def setup(self) -> None:
        import jax.numpy as jnp
        from repro.core import ChannelConfig, SchedulerConfig
        from repro.fl.client_shard import (init_schedule_carry,
                                           make_schedule_chunk_runner)

        cfg = self.cfg
        n = cfg["n_clients"]
        self.k_run = np.asarray(seed_key(self.seed))
        scfg = SchedulerConfig(n_clients=n, model_bits=cfg["model_bits"],
                               lam=cfg["lam"], V=cfg["V"],
                               q_floor=cfg["q_floor"],
                               guarantee_one=cfg["guarantee_one"])
        ch = ChannelConfig(n_clients=n, bandwidth_hz=cfg["bandwidth_hz"],
                           noise_power=cfg["noise_power"],
                           p_max=cfg["p_max"], p_bar=cfg["p_bar"])
        sig = jnp.asarray(self.ref.sigmas(cfg))
        self.run_chunk = make_schedule_chunk_runner(
            sig, scfg, ch, policy=cfg["policy"], solver=cfg["solver"],
            m_cap=cfg["sel_cap"])
        self.carry = init_schedule_carry(self.k_run, sig, ch,
                                         policy=cfg["policy"])
        for i in range(self.traffic["setup_chunks"]):
            self.carry, out = self.run_chunk(self.carry, self.rounds)
            rows = self._take(self.run_chunk.unpack(out))
            if i == 0:
                # the queues after the first chunk, before the next call
                # donates the carry
                self.first = dict(rows, z=np.asarray(self.carry[0].z))
        self.setup_rounds = self.traffic["setup_chunks"] * self.rounds

    # ------------------------------------------------------------ window
    def window(self, seconds: float) -> None:
        run_chunk, rounds, span = self.run_chunk, self.rounds, self.span
        unpack, take = run_chunk.unpack, self._take
        carry, chunks = self.carry, 0
        t0 = now()
        while True:
            with span("chunk"):
                carry, out = run_chunk(carry, rounds)
                out.copy_to_host_async()
                take(unpack(out))
            chunks += 1
            if now() - t0 >= seconds:
                break
        self.elapsed = now() - t0
        self.carry = carry
        self.chunks = chunks
        self.attempted = chunks * rounds

    def end_to_end(self) -> dict:
        return {"rounds_per_s": self.attempted / self.elapsed}

    def counters(self) -> dict:
        """Counts of the window's work for the per-layer readers: the
        fused decision's bytes and operations over every client of every
        round (``bench/counts.py``). The one-dimensional kernel reads no
        validity mask, so one byte a lane comes off the count."""
        lanes = self.cfg["n_clients"] * self.attempted
        nbytes, ops = counts.decision_work(lanes=lanes, rows=self.attempted)
        return dict(window_s=self.elapsed, rounds=self.attempted,
                    chunks=self.chunks, kernel_bytes=nbytes - lanes,
                    kernel_ops=ops)

    def release(self) -> None:
        self.end = dict(key=np.asarray(self.carry[2]))
        del self.carry, self.run_chunk

    # ------------------------------------------------------------ check
    def compare(self, control: bool = False, fault: str = "") -> dict:
        """The first chunk against the lanes followed from empty queues,
        the run key after the window and the overflow; with ``control``
        the reference's own chunk in bfloat16 stands in the program's
        place, and ``fault="frozen"`` leaves the carry as set-up found it
        (empty queues, the run key unchanged)."""
        cfg, k_run = self.cfg, self.k_run
        prog, key, overflow = self.first, self.end["key"], self.overflow
        if control:
            prog = self.ref.own_chunk(k_run, cfg, self.rounds, control=True)
            overflow = int(prog["overflow"].sum())
        if fault == "frozen":
            prog = dict(prog, z=np.zeros(cfg["n_clients"]))
            key = k_run
        out = self.ref.judge(k_run, cfg, prog)
        want = self.ref.key_after(k_run, self.setup_rounds + self.attempted)
        out["key_mismatch"] = int(np.sum(np.asarray(key).ravel()
                                         != want.ravel()))
        out["overflow"] = overflow
        out["max_n_sel"] = self.max_n_sel
        return out
