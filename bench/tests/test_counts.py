"""The FLOP and byte counters against hand counts, and the peak table."""

import pytest

import counts
import harness

VI_A = {"height": 32, "width": 32, "channels": 3, "conv1": 32, "conv2": 64,
        "hidden": 120, "n_classes": 10, "ksize": 5, "local_steps": 10,
        "batch": 32, "eval_size": 2000}


def test_cnn_forward_flops_hand_count():
    # conv1: 32*32 positions x 5*5*3 taps x 32 filters x 2
    # conv2: 16*16 x 5*5*32 x 64 x 2; dense 8*8*64 -> 120 and 120 -> 10
    hand = (2 * 1024 * 75 * 32 + 2 * 256 * 800 * 64 + 2 * 4096 * 120
            + 2 * 120 * 10)
    assert hand == 32_115_040
    assert counts.cnn_forward_flops(32, 32, 3, 32, 64, 120, 10, 5) == hand
    assert counts.cnn_train_flops(VI_A) == 3 * hand


def test_engine_useful_flops_counts_participants_and_evals():
    fwd = 32_115_040
    one = counts.engine_useful_flops(VI_A, participants=1, evals=0)
    assert one == 10 * 32 * 3 * fwd
    assert counts.engine_useful_flops(VI_A, 7, 2) == 7 * one + 2 * 2000 * fwd


def test_decision_work_hand_count():
    # reads gains, Z, u (f32) and the mask (1 B); writes sel (1 B) and
    # q, P, Z', comm time, power (f32); 14 f32 operands per row
    assert counts.DECISION_BYTES_PER_LANE == 12 + 1 + 1 + 20
    nbytes, ops = counts.decision_work(lanes=100, rows=3)
    assert nbytes == 100 * 34 + 3 * 56
    assert ops == 100 * counts.DECISION_OPS_PER_LANE


def test_least_time_names_its_bound():
    peaks = {"hbm_bytes_per_s": 1e9, "bf16_flops_per_s": 1e12}
    assert counts.least_time(1e9, 1e9, peaks) == (1.0, "memory")
    assert counts.least_time(1e6, 1e13, peaks) == (10.0, "compute")


def test_peaks_known_kind():
    p = harness.peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert "source" in p


def test_peaks_unknown_kind_is_an_error():
    with pytest.raises(KeyError, match="not in"):
        harness.peaks_for("TPU v9 imaginary")
