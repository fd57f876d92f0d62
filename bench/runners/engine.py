"""Runner of federated-engine configurations (``"runner": "engine"``).

The timed path is the scan engine's chunk runner
(``repro.fl.engine.make_chunk_runner``): back-to-back chunks of
``chunk_rounds`` rounds with the carry donated, each ending in
``block_until_ready``, as a coordinator that waits for each chunk's
accuracy would drive it (closed loop). Set-up makes the data and the
model's weights on the device from the seed, builds the runner and its
carry, and drives them through the first ``setup_chunks`` chunks, the
first of which compiles. The reference then follows the set-up's rounds
(``<config>_ref.py``), and its decision layer every round of the run; the
comparison (``checks.engine_gaps`` on the first chunk's update and the
set-up's rounds, ``checks.engine_decision_gaps`` on each set-up round's
decisions at the program's own queues, ``checks.engine_end_gaps`` on the
carry after the window) decides ``correct``.
"""

from __future__ import annotations

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import checks  # noqa: E402
import counts  # noqa: E402
from harness import now, seed_key, spans  # noqa: E402


def make_data(key, cfg):
    """The synthetic CIFAR-10-like federated data of the configuration, in
    one jitted call: every client draws its labels uniformly (i.i.d.
    partition), and an image is its class template plus Gaussian noise of
    standard deviation 2.5, so the classes are separable but noisy."""
    import jax
    import jax.numpy as jnp

    n, per = cfg["n_clients"], cfg["per_client"]
    shape = (cfg["height"], cfg["width"], cfg["channels"])
    classes = cfg["n_classes"]

    @jax.jit
    def make(key):
        k1, k2, k3, k4, k5 = jax.random.split(key, 5)
        tmpl = jax.random.normal(k1, (classes,) + shape)
        labels = jax.random.randint(k2, (n, per), 0, classes)
        imgs = tmpl[labels] + 2.5 * jax.random.normal(k3, (n, per) + shape)
        tl = jax.random.randint(k4, (cfg["n_test"],), 0, classes)
        ti = tmpl[tl] + 2.5 * jax.random.normal(k5, (cfg["n_test"],) + shape)
        return dict(client_images=imgs, client_labels=labels,
                    test_images=ti, test_labels=tl)

    return make(key)


def make_params(key, cfg):
    """The CNN's weights in the program's layout, in one jitted call:
    truncated-normal He initialisation of the kernels, zero biases."""
    import jax
    import jax.numpy as jnp

    k, c = cfg["ksize"], cfg["channels"]
    flat = (cfg["height"] // 4) * (cfg["width"] // 4) * cfg["conv2"]
    shapes = {"c1w": ((k, k, c, cfg["conv1"]), k * k * c),
              "c2w": ((k, k, cfg["conv1"], cfg["conv2"]),
                      k * k * cfg["conv1"]),
              "f1w": ((flat, cfg["hidden"]), flat),
              "f2w": ((cfg["hidden"], cfg["n_classes"]), cfg["hidden"])}
    biases = {"c1b": cfg["conv1"], "c2b": cfg["conv2"], "f1b": cfg["hidden"],
              "f2b": cfg["n_classes"]}

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(shapes))
        out = {name: jax.random.truncated_normal(kk, -2.0, 2.0, shape)
               * (2.0 / fan_in) ** 0.5
               for kk, (name, (shape, fan_in)) in zip(keys, shapes.items())}
        out.update({name: jnp.zeros((m,), jnp.float32)
                    for name, m in biases.items()})
        return out

    return make(key)


class Run:
    def __init__(self, cfg: dict, traffic: dict, seed: int, ref,
                 tracing: bool = False):
        self.cfg, self.traffic, self.seed, self.ref = cfg, traffic, seed, ref
        self.span = spans(tracing)
        if traffic["kind"] != "engine_chunks":
            raise ValueError(f"this runner reads engine_chunks traffic, not "
                             f"{traffic['kind']!r}")
        self.rounds = traffic["chunk_rounds"]
        if self.rounds != 1:
            raise ValueError("the decision layer is compared round by round "
                             "at the program's queues: chunk_rounds is 1")
        self.attempted = 0
        self.failed = 0
        self.chains = {}    # the reference's decision layer, by control

    # ------------------------------------------------------------ set-up
    def setup(self) -> None:
        import jax
        import jax.numpy as jnp
        from repro.core import ChannelConfig, SchedulerConfig
        from repro.data.synthetic import FederatedDataset
        from repro.fl.engine import SimConfig, init_carry, make_chunk_runner

        cfg = self.cfg
        if cfg["ksize"] != 5:
            raise ValueError("the program's CNN has 5x5 kernels")
        k_data, k_params, self.k_run = jax.random.split(seed_key(self.seed),
                                                        3)
        self.data = make_data(k_data, cfg)
        self.params0 = make_params(k_params, cfg)
        ds = FederatedDataset(n_classes=cfg["n_classes"], **self.data)
        sim = SimConfig(
            rounds=self.rounds, gamma=cfg["gamma"],
            local_steps=cfg["local_steps"], batch=cfg["batch"],
            m_cap=cfg["m_cap"], eval_every=cfg["eval_every"],
            eval_size=cfg["eval_size"], policy="proposed",
            solver=cfg["solver"], model="cnn",
            model_params=(("conv1", cfg["conv1"]), ("conv2", cfg["conv2"]),
                          ("hidden", cfg["hidden"])))
        scfg = SchedulerConfig(n_clients=cfg["n_clients"],
                               model_bits=cfg["model_bits"], lam=cfg["lam"],
                               V=cfg["V"], q_floor=cfg["q_floor"],
                               guarantee_one=cfg["guarantee_one"])
        ch = ChannelConfig(n_clients=cfg["n_clients"],
                           bandwidth_hz=cfg["bandwidth_hz"],
                           noise_power=cfg["noise_power"],
                           p_max=cfg["p_max"], p_bar=cfg["p_bar"])
        sig = jnp.asarray(self.ref.sigmas(cfg))
        self.run_chunk = make_chunk_runner(ds, sim, scfg, ch, sig)
        # the carry is donated: it gets its own copy of the run key
        self.carry = init_carry(jnp.array(self.k_run), self.params0, scfg,
                                sim, sig, ch)
        # host copies of the model after the first chunk and the last, and
        # of each round's queues and Eq. 8 sums, before the next call
        # donates the carry
        z_rounds, t_rounds, p_rounds = [], [], []
        t_sum = p_sum = 0.0
        for i in range(self.traffic["setup_chunks"]):
            self.carry, acc, _ = self.run_chunk(self.carry, self.rounds)
            jax.block_until_ready(self.carry)
            if i == 0:
                first = jax.tree.map(np.asarray, self.carry[0])
            z_rounds.append(np.asarray(self.carry[1].z))
            t, p = float(self.carry[4]), float(self.carry[5])
            t_rounds.append(t - t_sum)
            p_rounds.append(p - p_sum)
            t_sum, p_sum = t, p
        self.setup_out = dict(
            params_first=first,
            params=jax.tree.map(np.asarray, self.carry[0]),
            z_rounds=z_rounds, t_comm_rounds=t_rounds, power_rounds=p_rounds,
            acc=float(acc))
        self.setup_rounds = self.traffic["setup_chunks"] * self.rounds

    # ------------------------------------------------------------ window
    def window(self, seconds: float) -> None:
        import jax
        run_chunk, rounds, span = self.run_chunk, self.rounds, self.span
        carry, chunks = self.carry, 0
        t0 = now()
        while True:
            with span("chunk"):
                carry, acc, nsel = run_chunk(carry, rounds)
                jax.block_until_ready(carry)
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
        """Counts of the window's work for the per-layer readers: the real
        participants come from the reference's decision over the same
        rounds (a selection is a Bernoulli draw, so the count differs from
        the program's only where a draw sits on its threshold)."""
        per_round = self._chain()["participants"]
        parts = int(per_round[self.setup_rounds:].sum())
        return dict(window_s=self.elapsed, rounds=self.attempted,
                    chunks=self.chunks, participants=parts,
                    useful_flops=counts.engine_useful_flops(
                        self.cfg, parts, self.chunks))

    def release(self) -> None:
        c = self.carry
        self.end = dict(key=np.asarray(c[3]), z=np.asarray(c[1].z),
                        power=float(c[5]))
        del self.carry, self.run_chunk, c

    # ------------------------------------------------------------ check
    def reference(self, **kw) -> dict:
        return self.ref.run_rounds(self.params0, self.data, self.k_run,
                                   self.cfg, self.setup_rounds,
                                   first_rounds=self.rounds, **kw)

    def _chain(self, control: bool = False) -> dict:
        """The reference's decision layer over every round of the run."""
        if control not in self.chains:
            self.chains[control] = self.ref.decision_chain(
                self.k_run, self.cfg, self.setup_rounds + self.attempted,
                control=control)
        return self.chains[control]

    def compare(self, control: bool = False, fault: str = "") -> dict:
        """The set-up's rounds, and the carry after the window, against the
        reference; with ``control`` or ``fault`` the reference computed so
        stands in the program's place (a frozen state leaves the carry as
        set-up made it; the other faults leave the decision layer as it
        is)."""
        if not hasattr(self, "_ref"):
            self._ref = self.reference()
        ref = self._ref
        prog, end = self.setup_out, self.end
        if control or fault:
            prog = self.reference(control=control, fault=fault)
            end = self._chain(control)
            if fault == "frozen":
                end = dict(key=np.asarray(self.k_run),
                           z=np.zeros(self.cfg["n_clients"]), power=0.0)
        p0 = {k: np.asarray(v) for k, v in self.params0.items()}
        out = checks.engine_gaps(prog, ref, p0)
        follow = self.ref.follow_decisions(self.k_run, self.cfg,
                                           prog["z_rounds"])
        out.update(checks.engine_decision_gaps(prog, follow))
        out.update(checks.engine_end_gaps(end, self._chain()))
        return out
