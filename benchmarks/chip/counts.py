"""Operations and bytes the algorithms need, counted from shapes.

One function per score function gives the forward FLOPs of scoring ``n_q``
queries against ``n_c`` shared candidates through the joint decomposition
``score(q, c) = pairwise(o_q, c)`` (a (n_q, d) x (d, n_c) product plus the
vector work around it). Training counts the forward and backward of the
score function (backward = 2 x forward); nothing else of the step counts.
The sparse-Adagrad bytes are what any sparse update must move, whatever
implements it.
"""

from __future__ import annotations

MODES = 2  # head and tail corruption


def transe_l2_forward(n_q: int, n_c: int, d: int) -> float:
    """gamma - ||o - c||: o = e +- r (d), |o|^2 (2d), |c|^2 (2d per candidate),
    o.c (2d per pair), |o|^2 - 2 o.c + |c|^2 (3 per pair)."""
    return n_q * 3 * d + n_c * 2 * d + n_q * n_c * (2 * d + 3)


def distmult_forward(n_q: int, n_c: int, d: int) -> float:
    """<e, r, c>: o = e * r (d), o.c (2d per pair)."""
    return n_q * d + n_q * n_c * 2 * d


def transe_l2_positive(b: int, d: int) -> float:
    """gamma - ||h + r - t||: add, subtract, square and sum (4d)."""
    return b * 4 * d


def distmult_positive(b: int, d: int) -> float:
    """sum(h * r * t): two products and a sum (3d)."""
    return b * 3 * d


FORWARD = {"transe_l2": transe_l2_forward, "distmult": distmult_forward}
POSITIVE = {"transe_l2": transe_l2_positive, "distmult": distmult_positive}


def train_step_flops(model: str, batch_size: int, neg_size: int, n_groups: int,
                     dim: int) -> float:
    """Score-function FLOPs of one joint-negative training step, forward and
    backward: each of the ``n_groups`` groups scores its ``batch_size /
    n_groups`` triplets against its ``neg_size`` shared negatives, per mode."""
    g = batch_size // n_groups
    fwd = POSITIVE[model](batch_size, dim) + MODES * n_groups * FORWARD[model](
        g, neg_size, dim)
    return 3.0 * fwd


def sparse_adagrad_bytes(unique_rows: int, dim: int, itemsize: int = 4) -> float:
    """Least bytes of a sparse Adagrad update of ``unique_rows`` rows: read and
    write of the row and of its accumulator, and a read of the row's
    aggregated gradient (5 row transfers)."""
    return 5.0 * unique_rows * dim * itemsize
