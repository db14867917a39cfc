"""TransR's operations and bytes, counted from shapes.

The score function's FLOPs, as ``counts.py`` counts the others: the forward
of the definition gamma - ||e M_r + r - c M_r|| over a step's positives and
its joint negatives, backward = 2 x forward. The projected scoring (each
group's ``k`` shared candidates projected by the matrix of each of its
``b / n_groups`` triplets, and their distances to the triplet's vector) is
counted on its own with the least bytes it must move. Padding counts in
neither: this is the work, whatever implements it.
"""

from __future__ import annotations

MODES = 2  # head and tail corruption


def transr_positive(b: int, d: int, rd: int) -> float:
    """gamma - ||h M + r - t M||: two projections (2 d rd each), add and
    subtract (2 rd), square and sum (2 rd)."""
    return b * (4 * d * rd + 4 * rd)


def transr_projected(n_q: int, n_c: int, d: int, rd: int) -> float:
    """Squared distances of ``n_q`` vectors o (rd) to ``n_c`` candidates,
    each candidate projected by each query's own (d, rd) matrix: the
    projection (2 d rd) and subtract, square and sum (3 rd) per pair."""
    return n_q * n_c * (2 * d * rd + 3 * rd)


def transr_forward(n_q: int, n_c: int, d: int, rd: int) -> float:
    """One group and mode: o = e M +- r (2 d rd + rd per query), the
    projected distances, and gamma - sqrt (2 per pair)."""
    return n_q * (2 * d * rd + rd) + transr_projected(n_q, n_c, d, rd) + 2 * n_q * n_c


def train_step_flops(batch_size: int, neg_size: int, n_groups: int, dim: int,
                     rel_dim: int) -> float:
    """Score-function FLOPs of one joint-negative TransR step, forward and
    backward."""
    g = batch_size // n_groups
    fwd = transr_positive(batch_size, dim, rel_dim) + MODES * n_groups * transr_forward(
        g, neg_size, dim, rel_dim)
    return 3.0 * fwd


def projected_step_flops(batch_size: int, neg_size: int, n_groups: int, dim: int,
                         rel_dim: int) -> float:
    """FLOPs of one step's projected scoring, both modes, forward and backward."""
    g = batch_size // n_groups
    return 3.0 * MODES * n_groups * transr_projected(g, neg_size, dim, rel_dim)


def projected_step_bytes(batch_size: int, neg_size: int, n_groups: int, dim: int,
                         rel_dim: int, itemsize: int = 4) -> float:
    """Least bytes of one step's projected scoring, both modes, forward and
    backward: each triplet's matrix read once and its gradient written once;
    per mode, the vectors o and the candidates read, the distances written,
    their upstream gradient read, and the gradients of o and of the
    candidates written."""
    b, k, ng = batch_size, neg_size, n_groups
    matrices = 2 * b * dim * rel_dim
    per_mode = 2 * b * rel_dim + 2 * ng * k * dim + 2 * b * k
    return float(itemsize * (matrices + MODES * per_mode))
