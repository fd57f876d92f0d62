"""Federated rounds (Algorithm 1) — the paper's training loop, two scales.

``fl_round``: the generic q-weighted FedAvg round. Each client runs I local
SGD steps from the shared global model, then the server computes

    x_{t+1} = (1/N) sum_n (I_n / q_n) y_n                 (Algorithm 1, l.7)

implemented literally: every client's y_n = I local steps from x_t, and a
client contributes (I_n/q_n) y_n — zero when not sampled. Since
E[I_n/q_n] = 1 and sampling is independent of SGD noise, the aggregate is
an unbiased estimate of the all-client average (Theorem 1's requirement).
The paper notes the algorithm is "logically equivalent" to one where only
participants compute — on real hardware non-participants skip their round;
in the jitted simulation the masked compute keeps shapes static.

At pod scale (`make_fl_train_step`) the client axis is the mesh 'pod' axis:
params broadcast to per-pod replicas, vmapped local steps, and the weighted
mean over the pod dim lowers to the cross-pod all-reduce — the expensive,
*scheduled* collective the paper's Algorithm 2 controls.

`make_sharded_round_update` is that idea inside the simulation engines: the
<= m_cap sampled participants are sharded across a 'part' device mesh axis
(one `shard_map`, per-device `lax.map`, psum aggregate), with the
variance-reduced delta form putting `wire_dtype` (bf16) bytes on the
all-reduce wire. `SimConfig(participant_shards=D)` turns it on.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.fl.sharding import shard_map


def local_sgd(loss_fn: Callable, params, batches, gamma: float, steps: int):
    """I local SGD steps (Algorithm 1, lines 4-6).

    ``batches``: pytree whose leaves have leading dim ``steps`` (one
    minibatch per local iteration). Plain SGD, as in the paper.
    """

    def step(p, batch):
        g = jax.grad(loss_fn)(p, batch)
        return jax.tree.map(lambda w, gw: w - gamma * gw.astype(w.dtype),
                            p, g), None

    out, _ = jax.lax.scan(step, params, batches, length=steps)
    return out


def weighted_aggregate(global_params, client_params, selected, q):
    """Line 7 of Algorithm 1: x <- (1/N) sum_n (I_n/q_n) y_n.

    client_params: pytree with leading client axis; selected (N,) {0,1};
    q (N,) probabilities. fp32 accumulation.
    """
    n = q.shape[0]
    w = selected.astype(jnp.float32) / q / n                  # (N,)

    def agg(y):
        wf = w.reshape((n,) + (1,) * (y.ndim - 1))
        return jnp.sum(y.astype(jnp.float32) * wf, axis=0).astype(y.dtype)

    return jax.tree.map(agg, client_params)


def delta_aggregate(global_params, client_params, selected, q,
                    wire_dtype=jnp.bfloat16):
    """Beyond-paper aggregation: x <- x + (1/N) sum_n (I_n/q_n)(y_n - x).

    Same expectation as Algorithm 1 line 7 (E[I/q] = 1 makes the extra
    (1 - (1/N)Σ I/q) x term vanish in mean) but strictly lower variance —
    non-participating mass stays at x_t instead of being re-estimated —
    and the transmitted quantity is a small-dynamic-range DELTA, so it
    survives ``wire_dtype`` (bf16) compression: the cross-pod all-reduce
    moves half the bytes of the paper-literal fp32 parameter average.
    """
    n = q.shape[0]
    w = selected.astype(jnp.float32) / q / n

    def agg(x, y):
        wf = w.reshape((n,) + (1,) * (y.ndim - 1))
        # weight BEFORE the cross-client reduce and keep the summand in
        # wire_dtype: the pod all-reduce then moves bf16 on the links
        # (casting after the product would be fused away and the reduce
        # would silently stay fp32 — measured in §Perf iteration 1).
        delta = (y.astype(jnp.float32) - x.astype(jnp.float32)[None])
        update = jnp.sum((delta * wf).astype(wire_dtype), axis=0)
        return (x.astype(jnp.float32)
                + update.astype(jnp.float32)).astype(x.dtype)

    return jax.tree.map(agg, global_params, client_params)


def fl_round(loss_fn: Callable, params, client_batches, selected, q,
             gamma: float, steps: int):
    """One full round over an explicit client axis.

    client_batches: leaves (N, steps, ...). Local updates are computed for
    every client under vmap (non-participants' work is masked out by the
    aggregation weight — on real hardware non-participants simply skip; in
    the jitted simulation the masked compute keeps shapes static).
    """
    n = q.shape[0]
    bparams = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), params)
    updated = jax.vmap(lambda p, b: local_sgd(loss_fn, p, b, gamma, steps))(
        bparams, client_batches)
    return weighted_aggregate(params, updated, selected, q)


def pack_participants(sel, m_cap: int):
    """Pack the first ``m_cap`` selected clients to the front.

    ``sel`` is the (N,) selection mask; returns ``(sel_idx, sel_valid,
    overflow)`` — the packed (ascending) client indices, zero-filled past
    the selection count, the validity mask, and the int32 count of
    selected clients that did not fit. The single-device home of the
    packing the client-sharded engine reproduces with a per-shard pack +
    cross-shard merge (``fl/client_shard.py::_pack_participants_sharded``).

    Slot ``k`` holds the first lane whose prefix count of ``sel`` reaches
    ``k + 1``: a binary search of the (non-decreasing) count, so the ids
    are ``jnp.nonzero(sel, size=m_cap, fill_value=0)``'s, bit for bit,
    found by ``ceil(log2(N + 1))`` gathers of ``m_cap`` lanes (unrolled:
    no loop back-edge between them). ``nonzero`` builds them with a
    scatter-add of all N lanes into ``m_cap`` bins, which a TPU v5e
    serialises on the colliding bins: 8.75 ms a round at N = 10^6
    against 0.15 ms for the search.
    """
    count = jnp.cumsum(sel, dtype=jnp.int32)
    n_sel = count[-1]
    sel_valid = jnp.arange(m_cap) < n_sel
    rank = jnp.arange(1, m_cap + 1, dtype=jnp.int32)
    sel_idx = jnp.searchsorted(count, rank, side="left",
                               method="scan_unrolled")
    sel_idx = jnp.where(sel_valid, sel_idx, 0)
    overflow = (n_sel - jnp.minimum(n_sel, m_cap)).astype(jnp.int32)
    return sel_idx, sel_valid, overflow


def sample_batches(key, client_images, client_labels, sel_idx, m_cap: int,
                   steps: int, batch: int):
    """Draw the participants' local minibatches (one per local SGD step).

    Shared verbatim by the sequential round core and the client-sharded
    round — the (m_cap, steps, batch) index draw consumes the SAME key the
    same way in both, which the mesh-1 bitwise parity contract relies on.
    """
    per_client = client_labels.shape[1]
    idx = jax.random.randint(key, (m_cap, steps, batch), 0, per_client)
    imgs = client_images[sel_idx[:, None, None], idx]
    labs = client_labels[sel_idx[:, None, None], idx]
    return imgs, labs


def masked_aggregate(params, updated, sel_valid, q_sel, n_clients,
                     aggregation: str = "paper", wire_dtype=jnp.float32,
                     axis_name=None):
    """Algorithm 1 line 7 over the <= m_cap MATERIALIZED participants.

    The simulation-side form of :func:`weighted_aggregate` /
    :func:`delta_aggregate`: ``updated`` carries only the gathered
    participants (leading axis m_cap), masked by ``sel_valid`` and weighted
    by 1/(N q). ``wire_dtype`` applies to the delta form only — the
    per-participant weighted deltas are cast to it before the
    cross-participant sum (the quantity a real deployment puts on the
    wire). ``axis_name`` turns the local sum into a per-shard partial
    completed by a ``psum`` over that mesh axis — the participant-sharded
    round's collective; the cast-before-psum order is what puts
    ``wire_dtype`` bytes on the links. One home for this math: the scan
    engine (axis_name=None), the shard_map round (axis_name='part'), and
    the grid all call here. (The legacy loop engine keeps its own copy BY
    DESIGN — it is the independently-implemented parity reference.)
    """
    w = sel_valid.astype(jnp.float32) / jnp.maximum(q_sel, 1e-9) / n_clients

    def reduce(x):
        return x if axis_name is None else jax.lax.psum(x, axis_name)

    if aggregation == "delta":
        def agg(x, y):
            wf = w.reshape((-1,) + (1,) * (y.ndim - 1))
            delta = y.astype(jnp.float32) - x.astype(jnp.float32)[None]
            update = reduce(jnp.sum((delta * wf).astype(wire_dtype), axis=0))
            return x.astype(jnp.float32) + update.astype(jnp.float32)

        return jax.tree.map(agg, params, updated)

    def agg(y):
        wf = w.reshape((-1,) + (1,) * (y.ndim - 1))
        return reduce(jnp.sum(y.astype(jnp.float32) * wf, axis=0))

    return jax.tree.map(agg, updated)


def make_sharded_round_update(loss_fn: Callable, gamma: float, steps: int,
                              n_clients: int, n_shards: int, *,
                              aggregation: str = "paper",
                              wire_dtype=jnp.float32,
                              devices: Optional[list] = None,
                              mesh: Optional[Mesh] = None) -> Callable:
    """Participant-sharded round update: the <= m_cap materialized
    participants' local-SGD runs as ONE ``shard_map`` over a participant
    mesh axis, and the q-weighted Algorithm-1 aggregate lowers to a
    cross-device all-reduce (``psum``) — the *scheduled* collective the
    paper's Algorithm 2 prices.

    Returns ``update(params, inputs, labels, sel_valid, q_sel) ->
    new_params`` where ``inputs``/``labels`` carry the participant axis
    leading ((m_cap, steps, batch, ...)). Each of the ``n_shards`` devices
    runs its m_cap/n_shards participants sequentially under ``lax.map``
    (the conv-friendly idiom — vmapped convs hit XLA:CPU's grouped-conv
    slow path), reduces its shard to a partial weighted sum, and the
    ``psum`` over the 'part' axis completes line 7 of Algorithm 1.

    ``aggregation="delta"`` is the variance-reduced form of
    :func:`delta_aggregate`, and here its bf16 wire design finally meets a
    real wire: per-device partial delta sums are cast to ``wire_dtype``
    BEFORE the psum, so the cross-device all-reduce moves ``wire_dtype``
    (bf16 = half the bytes of the paper-literal fp32 average).
    ``wire_dtype=float32`` keeps the math identical to the sequential
    engine's.

    Parity contract (tests/test_round_sharded.py): at mesh size 1 the
    update is BITWISE-identical to the sequential ``lax.map`` + masked
    aggregate path — same trip count, same single-sum reduction, and a
    size-1 psum is the identity. Across mesh sizes the reduction is
    re-associated per shard, so trajectories agree only to ~1 ulp/round
    (amplified through training), like the grid's per-mesh contract.

    If m_cap is not a multiple of ``n_shards`` the participant axis is
    padded with zero-weight rows (``sel_valid=False``, q=1) — padded rows
    train on zero data and contribute exactly 0 to the aggregate.

    ``mesh`` rides a caller-owned mesh carrying a ``'part'`` axis of
    extent ``n_shards`` instead of building a private 1D one — the
    composed 2D round (``fl/sharding.py::make_mesh2d``) passes its shared
    ``('client', 'part')`` mesh here. The specs below name only
    ``'part'``, so any extra axes are implicitly replicated and the
    per-device program is identical to the private-mesh case.
    """
    if mesh is not None:
        if "part" not in mesh.axis_names:
            raise ValueError(f"shared mesh {mesh.axis_names} has no "
                             "'part' axis")
        if mesh.shape["part"] != n_shards:
            raise ValueError(
                f"n_shards={n_shards} != mesh 'part' extent "
                f"{mesh.shape['part']}")
    else:
        devices = list(devices if devices is not None else jax.devices())
        if not 1 <= n_shards <= len(devices):
            raise ValueError(f"n_shards={n_shards} needs 1..{len(devices)} "
                             f"of the available devices")
        mesh = Mesh(np.array(devices[:n_shards]), ("part",))

    def shard_body(params, inputs, labels, sel_valid, q_sel):
        updated = jax.lax.map(
            lambda b: local_sgd(loss_fn, params, b, gamma, steps),
            (inputs, labels))
        return masked_aggregate(params, updated, sel_valid, q_sel,
                                n_clients, aggregation, wire_dtype,
                                axis_name="part")

    sharded = shard_map(
        shard_body, mesh=mesh,
        in_specs=(P(), P("part"), P("part"), P("part"), P("part")),
        out_specs=P())

    def update(params, inputs, labels, sel_valid, q_sel):
        m = sel_valid.shape[0]
        pad = (-m) % n_shards
        if pad:
            inputs = jnp.concatenate(
                [inputs, jnp.zeros((pad,) + inputs.shape[1:],
                                   inputs.dtype)], axis=0)
            labels = jnp.concatenate(
                [labels, jnp.zeros((pad,) + labels.shape[1:],
                                   labels.dtype)], axis=0)
            sel_valid = jnp.concatenate(
                [sel_valid, jnp.zeros((pad,), sel_valid.dtype)])
            q_sel = jnp.concatenate([q_sel, jnp.ones((pad,), q_sel.dtype)])
        return sharded(params, inputs, labels, sel_valid, q_sel)

    return update


def make_fl_train_step(loss_fn: Callable, gamma: float, steps: int,
                       n_clients: int):
    """Pod-scale FL train step. batch leaves: (n_clients, steps, ...);
    q, selected: (n_clients,). Suitable for pjit with the client dim mapped
    to the mesh 'pod' axis."""

    def train_step(params, batch, selected, q):
        return fl_round(loss_fn, params, batch, selected, q, gamma, steps)

    return train_step


def make_train_step(loss_fn: Callable, gamma: float):
    """Plain (non-federated) SGD step — the single-pod baseline and the
    building block the roofline table measures."""

    def train_step(params, batch):
        loss, g = jax.value_and_grad(loss_fn)(params, batch)
        new_params = jax.tree.map(
            lambda w, gw: w - gamma * gw.astype(w.dtype), params, g)
        return new_params, loss

    return train_step
