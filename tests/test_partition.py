"""Graph (T3) and relation (T4) partitioning invariants."""

import numpy as np
from _hypothesis_compat import given, settings, strategies as st

from repro.core.graph_part import (
    cut_fraction, metis_like_partition, partition,
    random_partition,
)
from repro.core.rel_part import load_imbalance, relation_partition


def test_metis_beats_random_on_clustered(small_kg):
    m = metis_like_partition(small_kg.train, small_kg.n_entities, 4, seed=0)
    r = random_partition(small_kg.n_entities, 4, seed=0)
    cm = cut_fraction(small_kg.train, m)
    cr = cut_fraction(small_kg.train, r)
    assert cm < 0.75 * cr


def test_partition_balance(small_kg):
    part = metis_like_partition(small_kg.train, small_kg.n_entities, 4, seed=0)
    sizes = np.bincount(part, minlength=4)
    assert sizes.max() <= 1.1 * sizes.mean() + 2


def test_partition_book_bijective(small_kg):
    book = partition(small_kg.train, small_kg.n_entities, 4)
    rows = book.global_row(np.arange(small_kg.n_entities))
    assert len(np.unique(rows)) == small_kg.n_entities
    assert rows.max() < book.n_rows
    # row decomposes back to (part, local)
    assert (rows // book.rows_per_part == book.part_of).all()


@settings(max_examples=20, deadline=None)
@given(n=st.integers(10, 300), p=st.integers(1, 8), seed=st.integers(0, 5))
def test_partition_book_property(n, p, seed):
    rng = np.random.default_rng(seed)
    trip = rng.integers(0, n, size=(max(20, n), 3))
    trip[:, 1] = rng.integers(0, 5, size=trip.shape[0])
    book = partition(trip, n, p, method="metis", seed=seed)
    assert book.part_sizes.sum() == n
    assert (book.local_row < book.rows_per_part).all()
    rows = book.global_row(np.arange(n))
    assert len(np.unique(rows)) == n


# ----------------------------------------------------------------- relations
def test_relation_partition_assignment():
    counts = np.array([1000, 500, 400, 50, 40, 30, 20, 10, 5, 5])
    rp = relation_partition(counts, 4, seed=0)
    # every relation either owned or shared
    assert ((rp.owner >= 0) | (rp.slot >= 0)).all()
    owned = rp.owner >= 0
    # owned relations get unique (part, slot)
    keys = rp.owner[owned] * rp.slots_per_part + rp.slot[owned]
    assert len(np.unique(keys)) == owned.sum()
    assert load_imbalance(rp) < 1.6


def test_split_frequent_relations():
    """A relation with more triplets than a fair share must be split (T4)."""
    counts = np.array([10_000] + [10] * 50)
    rp = relation_partition(counts, 4, seed=0)
    assert rp.owner[0] == -1  # shared
    assert rp.n_shared >= 1
    assert (rp.owner[1:] >= 0).all()


@settings(max_examples=20, deadline=None)
@given(
    n_rel=st.integers(1, 100),
    p=st.integers(1, 8),
    seed=st.integers(0, 3),
)
def test_relation_partition_property(n_rel, p, seed):
    rng = np.random.default_rng(seed)
    counts = rng.integers(1, 1000, size=n_rel)
    rp = relation_partition(counts, p, seed=seed)
    owned = rp.owner >= 0
    assert (rp.slot[owned] < rp.slots_per_part).all()
    assert (rp.owner[owned] < p).all()
    # shared slots are unique
    sh = ~owned
    if sh.any():
        assert len(np.unique(rp.slot[sh])) == sh.sum()


def test_epoch_randomization_differs():
    counts = np.ones(64, dtype=np.int64) * 10
    a = relation_partition(counts, 4, seed=0)
    b = relation_partition(counts, 4, seed=1)
    assert (a.owner != b.owner).any()
