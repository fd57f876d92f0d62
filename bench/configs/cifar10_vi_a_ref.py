"""Plain reference of the paper's Section VI-A federated rounds.

Each round (Algorithm 1 with Algorithm 2 scheduling):

1. the round key splits into a channel, a selection and a batch key;
2. every client's gain is |h|^2 = -2 sigma^2 ln u, clipped to the
   modulation band [(2^0.25 - 1) N0 / Pmax, (2^10 - 1) N0 / Pbar];
3. the Theorem-2 decision, selection, Eq. 9 queue update and Eq. 8
   accounting (``theorem2_ref``, float64 or the control's bfloat16);
4. the first ``m_cap`` selected clients, in client order, each run I local
   SGD steps from the global model on minibatches of their own data, the
   j-th participant taking the j-th row of a (m_cap, I, batch) index draw;
5. the server keeps x = sum_j y_j / (N q_j) (Algorithm 1, line 7).

The CNN is the paper's: 5x5 SAME convolutions with ReLU and 2x2 max
pooling, one hidden dense layer with ReLU, a linear output and mean
cross-entropy. It is written with ``lax.conv_general_dilated`` and
``reduce_window``, at the configuration's stated precision: float32 with
convolutions and matmuls at the TPU's default precision (one bfloat16
pass, float32 accumulation), or entirely in bfloat16 for the control.
The key chain and the random draws are part of the experiment's
definition, so they are drawn here with ``jax.random`` exactly as the
experiment states them.
"""

from __future__ import annotations

import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np

_spec = importlib.util.spec_from_file_location(
    "theorem2_ref", os.path.join(os.path.dirname(__file__), "theorem2_ref.py"))
t2 = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(t2)

F64 = np.float64
# Where a client's two Theorem-2 candidates lie within this share of each
# other in Eq. (15)'s objective, both are optimal to round-off and a
# float32 program may keep either (PERF.md, section 6).
TIE = 1e-3


def sigmas(cfg) -> np.ndarray:
    """Per-client Rayleigh scales: the stated fractions, rounded, the last
    group taking the remainder."""
    n = cfg["n_clients"]
    counts = [int(round(f * n)) for f in cfg["sigma_fracs"]]
    counts[-1] = n - sum(counts[:-1])
    return np.concatenate([np.full(c, s, np.float32)
                           for c, s in zip(counts, cfg["sigma_values"])])


def tenant(cfg) -> "t2.Tenant":
    col = lambda v: np.array([[v]], F64)  # noqa: E731
    return t2.Tenant(n=col(cfg["n_clients"]), v=col(cfg["V"]),
                     lam=col(cfg["lam"]), ell=col(cfg["model_bits"]),
                     bw=col(cfg["bandwidth_hz"]), n0=col(cfg["noise_power"]),
                     p_max=col(cfg["p_max"]), p_bar=col(cfg["p_bar"]),
                     q_floor=col(cfg["q_floor"]))


def gains_of(u, sig, cfg) -> np.ndarray:
    lo = (2.0 ** 0.25 - 1.0) * cfg["noise_power"] / cfg["p_max"]
    hi = (2.0 ** 10 - 1.0) * cfg["noise_power"] / cfg["p_bar"]
    g = -2.0 * sig.astype(F64) ** 2 * np.log(np.asarray(u, F64))
    return np.clip(g, lo, hi)


def round_keys(key):
    key, k = jax.random.split(key)
    k_ch, k_sel, k_bat = jax.random.split(k, 3)
    return key, k_ch, k_sel, k_bat


def draws(key, cfg):
    """One round's key split and draws: -> (key', gains, selection
    uniforms, k_bat), the gains and uniforms (1, n) host arrays."""
    n = cfg["n_clients"]
    key, k_ch, k_sel, k_bat = round_keys(key)
    u_ch = np.asarray(jax.random.uniform(k_ch, (n,), jnp.float32,
                                         minval=1e-12, maxval=1.0))
    u_sel = np.asarray(jax.random.uniform(k_sel, (n,)))
    return key, gains_of(u_ch, sigmas(cfg), cfg)[None], u_sel[None], k_bat


def decide(key, z, cfg, dtype=F64):
    """One round's draws and decision: -> (key', sel, q, p, t_comm, power,
    z', k_bat), the decision as float64 (or bfloat16) host arrays."""
    key, g, u_sel, k_bat = draws(key, cfg)
    sel, q, p, t_comm, power, z_new = t2.proposed(
        u_sel, g, np.asarray(z, dtype)[None], tenant(cfg),
        cfg["guarantee_one"], dtype)
    return key, sel[0], q[0], p[0], t_comm[0], power[0], z_new[0], k_bat


def follow_decisions(key, cfg, z_rounds) -> dict:
    """The decision layer over len(z_rounds) rounds, round r decided at
    the queues ``z_rounds[r - 1]`` (the program's after the round before;
    empty queues before the first), with the run key's draws.

    Per round: ``z_kept``, the queues Eq. 9 gives from the kept candidate;
    ``tie``, the clients whose two candidates (interior and Pmax) lie
    within ``TIE`` of each other in Eq. (15)'s objective, so that either
    is optimal to round-off, and ``z_other``, Eq. 9 from the other one;
    ``t_comm`` and ``power``, (least, kept, most) of the round's Eq. 8 sums
    over the choices on the tie clients."""
    t, p_bar = tenant(cfg), cfg["p_bar"]
    z = np.zeros((1, cfg["n_clients"]), F64)
    out = {k: [] for k in ("z_kept", "z_other", "tie", "t_comm", "power")}
    for z_next in z_rounds:
        key, g, u_sel, _ = draws(key, cfg)
        (q_i, p_i, f_i), (q_b, p_b, f_b) = t2.candidates(g, z, t)
        use_int = np.isfinite(f_i) & (f_i <= f_b)
        tie = np.isfinite(f_i) & (np.abs(f_i - f_b) <= TIE * np.abs(f_b))
        q_k, p_k = np.where(use_int, q_i, q_b), np.where(use_int, p_i, p_b)
        q_o, p_o = np.where(use_int, q_b, q_i), np.where(use_int, p_b, p_i)
        sel = t2.select(u_sel, q_k, cfg["guarantee_one"])
        out["z_kept"].append(t2.queue_update(z, q_k, p_k, p_bar)[0])
        out["z_other"].append(t2.queue_update(z, q_o, p_o, p_bar)[0])
        out["tie"].append(tie[0])
        for name, k, o in zip(("t_comm", "power"),
                              t2.lane_account(sel, q_k, p_k, g, t),
                              t2.lane_account(sel, q_o, p_o, g, t)):
            o = np.where(tie, o, k)
            out[name].append((float(np.minimum(k, o).sum()), float(k.sum()),
                              float(np.maximum(k, o).sum())))
        z = np.asarray(z_next, F64)[None]
    return out


def decision_chain(key, cfg, rounds: int, control: bool = False) -> dict:
    """The decision layer alone over ``rounds`` rounds from fresh queues
    (in bfloat16 for the control): -> the run ``key`` after them, the
    queues ``z``, the summed expected ``power`` and the ``participants``
    of each round, capped at m_cap."""
    dtype = __import__("ml_dtypes").bfloat16 if control else F64
    z = np.zeros(cfg["n_clients"], F64)
    power, parts = 0.0, []
    for _ in range(rounds):
        key, sel, _, _, _, pw, z, _ = decide(key, z, cfg, dtype)
        power += float(pw)
        parts.append(min(int(sel.sum()), cfg["m_cap"]))
    return dict(key=np.asarray(key), z=np.asarray(z, F64), power=power,
                participants=np.asarray(parts))


def apply_cnn(params, x, precision):
    dn = ("NHWC", "HWIO", "NHWC")

    def conv(x, w, b):
        y = jax.lax.conv_general_dilated(x, w, (1, 1), "SAME",
                                         dimension_numbers=dn,
                                         precision=precision)
        return jax.nn.relu(y + b)

    def pool(x):
        return jax.lax.reduce_window(x, -jnp.inf,
                                     jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1),
                                     "VALID")

    x = pool(conv(x, params["c1w"], params["c1b"]))
    x = pool(conv(x, params["c2w"], params["c2b"]))
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(jnp.dot(x, params["f1w"], precision=precision)
                    + params["f1b"])
    return jnp.dot(x, params["f2w"], precision=precision) + params["f2b"]


def loss(params, images, labels, precision):
    logits = apply_cnn(params, images, precision)
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))


@functools.partial(jax.jit, static_argnames=("gamma", "precision", "half"))
def local_sgd(params, images, labels, gamma, precision, half=False):
    """I plain SGD steps over (I, batch, ...) minibatches. ``half`` keeps
    only the first half of each minibatch (a planted fault)."""
    if half:
        b = images.shape[1] // 2
        images, labels = images[:, :b], labels[:, :b]

    def step(i, p):
        g = jax.grad(loss)(p, images[i], labels[i], precision)
        return jax.tree.map(lambda w, gw: w - jnp.asarray(gamma, w.dtype)
                            * gw.astype(w.dtype), p, g)

    return jax.lax.fori_loop(0, images.shape[0], step, params)


@functools.partial(jax.jit, static_argnames=("precision",))
def accuracy(params, images, labels, precision):
    logits = apply_cnn(params, images, precision)
    return jnp.mean(jnp.argmax(logits, -1) == labels)


@functools.partial(jax.jit, static_argnames=("precision",))
def first_grad_norms(params, images, labels, precision):
    g = jax.grad(loss)(params, images, labels, precision)
    return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(x.astype(jnp.float32)
                                                   ** 2)), g)


def run_rounds(params, data, key, cfg, rounds: int, first_rounds: int = 1,
               control: bool = False, fault: str = ""):
    """``rounds`` federated rounds from ``params`` and fresh queues.

    ``data`` holds ``client_images``, ``client_labels``, ``test_images``,
    ``test_labels`` (device arrays). Returns a dict with the final
    ``params``, each round's queues after it (``z_rounds``) and its summed
    ``t_comm_rounds`` and ``power_rounds``, the test ``acc`` after the
    last round, ``grad_norms``: each leaf's gradient
    norm at the first local step of the first round's first participant,
    ``params_first``: the model after the first ``first_rounds`` rounds,
    and ``first_scale``: the product over those rounds of
    sum_j 1 / (N q_j), the factor by which aggregation alone scales the
    model (x' = x sum_j 1 / (N q_j) + sum_j (y_j - x) / (N q_j)).

    ``control`` computes everything in bfloat16; ``fault`` plants one of
    ``"frozen"`` (the round returns its state unchanged), ``"half_batch"``
    (local steps see half of each minibatch) or ``"flip"`` (the first
    round's first unselected client is selected too).
    """
    n, m_cap = cfg["n_clients"], cfg["m_cap"]
    steps, batch = cfg["local_steps"], cfg["batch"]
    per_client = data["client_labels"].shape[1]
    dtype = jnp.bfloat16 if control else jnp.float32
    dec_dtype = __import__("ml_dtypes").bfloat16 if control else F64
    prec = jax.lax.Precision.DEFAULT
    params = jax.tree.map(lambda x: jnp.asarray(x, dtype), params)
    z = np.zeros(n, F64)
    z_rounds, t_rounds, p_rounds = [], [], []
    grad_norms = first = None
    scale = 1.0
    for r in range(rounds):
        key, sel, q, p, t_comm, power, z_new, k_bat = decide(key, z, cfg,
                                                             dec_dtype)
        if fault == "flip" and r == 0:
            sel = sel.copy()
            sel[int(np.argmin(sel))] = True
        idx = np.asarray(jax.random.randint(k_bat, (m_cap, steps, batch), 0,
                                            per_client))
        parts = np.flatnonzero(sel)[:m_cap]
        if r < first_rounds:
            scale *= float(np.sum(1.0 / (n * np.asarray(q, F64)[parts])))
        new = None
        for j, c in enumerate(parts):
            im = data["client_images"][c][idx[j]].astype(dtype)
            lb = data["client_labels"][c][idx[j]]
            if grad_norms is None:
                grad_norms = jax.tree.map(float, first_grad_norms(
                    params, im[0], lb[0], prec))
            y = local_sgd(params, im, lb, gamma=cfg["gamma"], precision=prec,
                          half=fault == "half_batch")
            w = jnp.asarray(1.0 / (n * float(q[c])), dtype)
            term = jax.tree.map(lambda a: a * w, y)
            new = term if new is None else jax.tree.map(jnp.add, new, term)
        if fault != "frozen":
            params = new
            z = np.asarray(z_new, F64)
        z_rounds.append(z)
        t_rounds.append(0.0 if fault == "frozen" else float(t_comm))
        p_rounds.append(0.0 if fault == "frozen" else float(power))
        if r + 1 == first_rounds:
            first = params
    ev = cfg["eval_size"]
    acc = float(accuracy(params, data["test_images"][:ev].astype(dtype),
                         data["test_labels"][:ev], prec))
    host = lambda p: jax.tree.map(  # noqa: E731
        lambda x: np.asarray(x, np.float32), p)
    return dict(params=host(params), params_first=host(first),
                first_scale=scale, z_rounds=z_rounds, t_comm_rounds=t_rounds,
                power_rounds=p_rounds, acc=acc, grad_norms=grad_norms)
