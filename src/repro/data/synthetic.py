"""Synthetic federated datasets (the container is offline — no downloads).

The *learning problem* is synthetic but keeps the paper's federated
structure:

* ``make_cifar10_like`` — N=100 clients, i.i.d. uniform partition of a
  10-class 32x32x3 problem (Section VI-A's setup).
* ``make_femnist_like`` — N=3597 "writers", 62 classes, non-i.i.d.: each
  client's data comes from ONE writer, modeled as a writer-specific affine
  style transform + a writer-biased label distribution (paper VI-B's
  one-writer-per-device partitioning).

Classes are separable-but-noisy class templates so the paper's CNN actually
learns: test accuracy rises well above chance within a few hundred rounds,
which is what the time-to-accuracy comparisons (Figs. 2-4) need.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["client_images", "client_labels",
                                "test_images", "test_labels"],
                   meta_fields=["n_classes"])
@dataclasses.dataclass
class FederatedDataset:
    """Client-partitioned dataset with a common test split.

    Image problems: ``client_images`` (N, per_client, H, W, C) float,
    ``client_labels`` (N, per_client) int32. Token problems
    (``make_lm_federated``): ``client_images`` (N, per_client, S) int32
    token sequences, ``client_labels`` the matching (N, per_client, S)
    next-token targets — the engines only ever index the leading two axes,
    so both layouts flow through the same round machinery.

    A pytree (``n_classes`` static), so the engines pass it through their
    jit boundary as an argument: a closed-over dataset would be baked into
    the compiled program as a constant.
    """

    client_images: jax.Array     # (N, per_client, H, W, C) | (N, per_client, S)
    client_labels: jax.Array     # (N, per_client) | (N, per_client, S) int32
    test_images: jax.Array       # (T, H, W, C) | (T, S)
    test_labels: jax.Array       # (T,) | (T, S) int32
    n_classes: int

    @property
    def n_clients(self) -> int:
        return self.client_images.shape[0]


def _class_templates(key, n_classes, h, w, c):
    return jax.random.normal(key, (n_classes, h, w, c))


def _render(key, templates, labels, noise=2.5):
    """Noisy class templates: SNR tuned so the paper CNN needs hundreds of
    rounds to approach its accuracy ceiling (time-to-accuracy curves need a
    non-trivial learning trajectory)."""
    imgs = templates[labels]
    return imgs + noise * jax.random.normal(key, imgs.shape)


def make_cifar10_like(key, n_clients: int = 100, per_client: int = 500,
                      n_test: int = 10000, h: int = 32, w: int = 32,
                      c: int = 3, n_classes: int = 10) -> FederatedDataset:
    """i.i.d. partition: every client draws labels uniformly (paper VI-A)."""
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    tmpl = _class_templates(k1, n_classes, h, w, c)
    labels = jax.random.randint(k2, (n_clients, per_client), 0, n_classes)
    imgs = _render(k3, tmpl, labels)
    tl = jax.random.randint(k4, (n_test,), 0, n_classes)
    ti = _render(k5, tmpl, tl)
    return FederatedDataset(client_images=imgs, client_labels=labels,
                            test_images=ti, test_labels=tl,
                            n_classes=n_classes)


def make_femnist_like(key, n_clients: int = 3597, per_client: int = 40,
                      n_test: int = 10000, h: int = 28, w: int = 28,
                      c: int = 1, n_classes: int = 62) -> FederatedDataset:
    """Non-i.i.d. one-writer-per-client: writer-specific style (affine
    transform of the canvas) + writer-biased label mix (Dirichlet 0.3)."""
    keys = jax.random.split(key, 7)
    tmpl = _class_templates(keys[0], n_classes, h, w, c)
    # Writer style: per-client gain/offset field.
    gain = 1.0 + 0.3 * jax.random.normal(keys[1], (n_clients, 1, 1, 1, 1))
    offset = 0.3 * jax.random.normal(keys[2], (n_clients, 1, h, w, c))
    # Writer-biased labels via Dirichlet mixing.
    alpha = jnp.full((n_classes,), 0.3)
    mix = jax.random.dirichlet(keys[3], alpha, (n_clients,))
    labels = jax.vmap(
        lambda k, p: jax.random.choice(k, n_classes, (per_client,), p=p))(
            jax.random.split(keys[4], n_clients), mix)
    imgs = _render(keys[5], tmpl, labels)
    imgs = imgs * gain + offset
    tl = jax.random.randint(keys[6], (n_test,), 0, n_classes)
    ti = _render(jax.random.fold_in(keys[6], 1), tmpl, tl)
    return FederatedDataset(client_images=imgs, client_labels=labels,
                            test_images=ti, test_labels=tl,
                            n_classes=n_classes)


def gather_batches(ds: FederatedDataset, key, steps: int, batch: int):
    """Draw per-client local-step minibatches: returns (images, labels) with
    shapes (N, steps, batch, H, W, C) / (N, steps, batch)."""
    n, per_client = ds.client_labels.shape
    idx = jax.random.randint(key, (n, steps, batch), 0, per_client)
    imgs = jax.vmap(lambda im, ix: im[ix])(
        ds.client_images, idx.reshape(n, -1))
    labs = jax.vmap(lambda lb, ix: lb[ix])(
        ds.client_labels, idx.reshape(n, -1))
    h, w, c = ds.client_images.shape[-3:]
    return (imgs.reshape(n, steps, batch, h, w, c),
            labs.reshape(n, steps, batch))


def make_token_stream(key, batch: int, seq: int, vocab: int):
    """Synthetic LM batch: a noisy copy task so loss visibly decreases."""
    k1, _ = jax.random.split(key)
    tokens = jax.random.randint(k1, (batch, seq), 0, vocab)
    labels = jnp.roll(tokens, -1, axis=1)
    return tokens, labels


def make_lm_federated(key, n_clients: int = 40, per_client: int = 32,
                      seq: int = 16, vocab: int = 32,
                      n_test: int = 512) -> FederatedDataset:
    """Federated token streams for ``model="transformer_lm"``.

    Same container as the image datasets — ``client_images`` holds the
    (N, per_client, seq) int32 token sequences and ``client_labels`` the
    matching next-token targets (``make_token_stream``'s roll convention),
    so the engines' gather/batch plumbing works unchanged. Non-iid like
    ``make_femnist_like``: each client draws tokens from its own
    Dirichlet(0.3) unigram mix, so the global model has learnable marginal
    structure (accuracy rises above 1/vocab) while clients disagree — the
    regime where Algorithm 1's unbiased 1/q weighting actually matters.
    """
    keys = jax.random.split(key, 3)
    alpha = jnp.full((vocab,), 0.3)
    mix = jax.random.dirichlet(keys[0], alpha, (n_clients,))
    tokens = jax.vmap(
        lambda k, p: jax.random.choice(k, vocab, (per_client, seq), p=p))(
            jax.random.split(keys[1], n_clients), mix)
    tokens = tokens.astype(jnp.int32)
    targets = jnp.roll(tokens, -1, axis=-1)
    # test split: the global mixture (uniform over clients' mixes)
    test_mix = jnp.mean(mix, axis=0)
    test_tokens = jax.random.choice(keys[2], vocab, (n_test, seq),
                                    p=test_mix).astype(jnp.int32)
    test_targets = jnp.roll(test_tokens, -1, axis=-1)
    return FederatedDataset(client_images=tokens, client_labels=targets,
                            test_images=test_tokens,
                            test_labels=test_targets, n_classes=vocab)
