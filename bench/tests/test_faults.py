"""A run with the timed path broken underneath comes out not correct.

Each test drives a whole tiny run on the CPU (the look for a chip
skipped) with one fault planted in the program: a step that returns its
state unchanged (from the first chunk, or only in the window), half of
the batch left out, an answer altered where it is produced. (The cells
run on one chip, so there is no exchange between chips to leave out.)"""

import jax
import jax.numpy as jnp
import pytest

from conftest import run_cell


def test_sound_runs_are_correct(tiny_root, capsys):
    for cell in ("tiny_engine_cell", "tiny_service_cell",
                 "tiny_service_all_cell"):
        rc, res = run_cell(tiny_root, cell, capsys)
        assert rc == 0 and res["correct"], res["checks"]
        assert list(res["checks"])[0] == "window_compiles"


def _engine_frozen(monkeypatch):
    from repro.fl import engine
    real = engine.make_chunk_runner

    def make(*a, **k):
        run_chunk = real(*a, **k)

        def frozen(carry, n_rounds):
            _, acc, nsel = run_chunk(jax.tree.map(jnp.copy, carry), n_rounds)
            return carry, acc, nsel
        return frozen
    monkeypatch.setattr(engine, "make_chunk_runner", make)


def _engine_frozen_in_window(monkeypatch):
    """Chunks after the first two return their carry unchanged: the
    set-up chunks run, the window's do not."""
    from repro.fl import engine
    real = engine.make_chunk_runner

    def make(*a, **k):
        run_chunk = real(*a, **k)
        calls = []

        def frozen_later(carry, n_rounds):
            calls.append(n_rounds)
            if len(calls) <= 2:
                return run_chunk(carry, n_rounds)
            _, acc, nsel = run_chunk(jax.tree.map(jnp.copy, carry), n_rounds)
            return carry, acc, nsel
        return frozen_later
    monkeypatch.setattr(engine, "make_chunk_runner", make)


def _engine_half_batch(monkeypatch):
    """Local SGD sees the first half of each minibatch, the loss its mean
    over that half."""
    from repro.fl import engine
    real = engine.sample_batches

    def half(*a, **k):
        imgs, labs = real(*a, **k)
        b = imgs.shape[2] // 2
        return imgs[:, :, :b], labs[:, :, :b]
    monkeypatch.setattr(engine, "sample_batches", half)


def _engine_altered(monkeypatch):
    from repro.fl import engine
    real = engine.pack_participants
    monkeypatch.setattr(engine, "pack_participants",
                        lambda sel, m_cap: real(sel.at[0].set(True), m_cap))


def _service_frozen(monkeypatch):
    from repro.service import batching
    real = batching.make_bucket_step

    def make(*a, **k):
        step = real(*a, **k)

        def frozen(state, *args):
            out = step(jax.tree.map(jnp.copy, state), *args)
            return out[:-1] + (state,)
        return frozen
    monkeypatch.setattr(batching, "make_bucket_step", make)


def _service_half_batch(monkeypatch):
    from repro.service.batching import SchedulerService
    real = SchedulerService.flush

    def flush(self, *a, **k):
        out = real(self, *a, **k)
        return dict(list(out.items())[: (len(out) + 1) // 2])
    monkeypatch.setattr(SchedulerService, "flush", flush)


def _service_altered(monkeypatch):
    from repro.service.batching import SchedulerService
    real = SchedulerService.flush

    def flush(self, *a, **k):
        out = real(self, *a, **k)
        return {name: d._replace(q=d.q * 1.01) for name, d in out.items()}
    monkeypatch.setattr(SchedulerService, "flush", flush)


FAULTS = {
    "engine_state_unchanged": ("tiny_engine_cell", _engine_frozen),
    "engine_state_unchanged_in_window": ("tiny_engine_cell",
                                         _engine_frozen_in_window),
    "engine_half_batch": ("tiny_engine_cell", _engine_half_batch),
    "engine_answer_altered": ("tiny_engine_cell", _engine_altered),
    "service_state_unchanged": ("tiny_service_cell", _service_frozen),
    "service_half_batch": ("tiny_service_all_cell", _service_half_batch),
    "service_answer_altered": ("tiny_service_cell", _service_altered),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_is_not_correct(fault, tiny_root, capsys,
                                      monkeypatch):
    cell, plant = FAULTS[fault]
    plant(monkeypatch)
    rc, res = run_cell(tiny_root, cell, capsys)
    assert rc == 0
    assert res["correct"] is False, res["checks"]
