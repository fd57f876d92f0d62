"""Operations and bytes of the measured work, counted from shapes.

Kept with the benchmark so that every PR counts the same work the same
way, whatever implements it.
"""

from __future__ import annotations


def cnn_forward_flops(height: int, width: int, channels: int, conv1: int,
                      conv2: int, hidden: int, n_classes: int,
                      ksize: int) -> int:
    """Forward FLOPs of one example through the paper's CNN: two SAME
    ``ksize`` x ``ksize`` convolutions (the second after a 2x2 pool), a
    hidden dense layer after the second pool and the output layer. Two
    FLOPs per multiply-add; biases, ReLU, pooling and softmax are left out
    (they are under 1% of the total)."""
    kk = ksize * ksize
    c1 = 2 * height * width * kk * channels * conv1
    c2 = 2 * (height // 2) * (width // 2) * kk * conv1 * conv2
    f1 = 2 * (height // 4) * (width // 4) * conv2 * hidden
    f2 = 2 * hidden * n_classes
    return c1 + c2 + f1 + f2


def cnn_train_flops(cfg: dict) -> int:
    """FLOPs of one training example: forward plus backward (twice the
    forward: the gradients of activations and of weights)."""
    return 3 * cnn_forward_flops(cfg["height"], cfg["width"], cfg["channels"],
                                 cfg["conv1"], cfg["conv2"], cfg["hidden"],
                                 cfg["n_classes"], cfg["ksize"])


def engine_useful_flops(cfg: dict, participants: int, evals: int) -> int:
    """The useful work of a stretch of federated rounds: the local SGD of
    the real participants (``participants`` summed over the rounds; padded
    slots do not count) and ``evals`` evaluations of ``eval_size`` test
    images."""
    per_participant = cfg["local_steps"] * cfg["batch"] * cnn_train_flops(cfg)
    fwd = cnn_train_flops(cfg) // 3
    return participants * per_participant + evals * cfg["eval_size"] * fwd


# The fused decision kernel, per real lane. Bytes: it reads gains, Z and
# the selection uniform (f32 each) and the validity mask (1 byte), and
# writes the selection (1 byte) and q, P, Z', the comm-time summand and
# the power summand (f32 each). Each tenant row also reads its 14 f32
# operands.
DECISION_BYTES_PER_LANE = 3 * 4 + 1 + 1 + 5 * 4
DECISION_BYTES_PER_ROW = 14 * 4

# Operations per lane of the algorithm (an elementwise op or a
# transcendental counts one): the interior candidate's argument and square
# root (5), the Lambert-W initial guess (8) and 4 Halley steps of 11 each
# (44), the interior power (7) and its clip (2), two Eq. 17 evaluations of
# 11 each (22), two Eq. 15 objectives of 12 each (24), the keep decision
# and the two selects (5), the selection (1), the Eq. 9 update (4), and
# the two accounting summands (rate, comm time, power: 8).
DECISION_OPS_PER_LANE = 5 + 8 + 4 * 11 + 7 + 2 + 22 + 24 + 5 + 1 + 4 + 8


def decision_work(lanes: int, rows: int) -> tuple:
    """(bytes, operations) of the fused decision over ``lanes`` real lanes
    in ``rows`` tenant rows."""
    return (lanes * DECISION_BYTES_PER_LANE + rows * DECISION_BYTES_PER_ROW,
            lanes * DECISION_OPS_PER_LANE)


def least_time(nbytes: float, ops: float, peaks: dict) -> tuple:
    """The roofline's least time and which bound sets it."""
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    t_ops = ops / peaks["bf16_flops_per_s"]
    return (t_mem, "memory") if t_mem >= t_ops else (t_ops, "compute")
