"""Synthetic knowledge graph of a stated size, generated from a seed.

A copy of the program's ``repro.data.kg_synth.make_synthetic_kg``, kept with
the benchmark so that no change to the program can move the traffic it is
measured on. Entities get clustered latent points, relations translate them,
each triplet's tail is the nearest of 32 candidates drawn from the target's
cluster, and heads and relations are drawn with Zipf-like skew.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Graph:
    n_entities: int
    n_relations: int
    triplets: np.ndarray  # (E, 3) [h, r, t], int64
    train: np.ndarray
    valid: np.ndarray
    test: np.ndarray


def make_graph(n_entities: int, n_relations: int, n_edges: int, seed: int,
               n_clusters: int = 16, latent_dim: int = 16,
               zipf_a: float = 0.8, cross_cluster_frac: float = 0.1,
               valid_frac: float = 0.05, test_frac: float = 0.05) -> Graph:
    rng = np.random.default_rng(seed)

    centers = rng.normal(0, 4.0, size=(n_clusters, latent_dim))
    cluster_of = rng.integers(0, n_clusters, size=n_entities)
    latent = centers[cluster_of] + rng.normal(0, 1.0, size=(n_entities, latent_dim))

    v = rng.normal(0, 0.6, size=(n_relations, latent_dim))
    jump = rng.random(n_relations) < cross_cluster_frac
    tgt_cluster = rng.integers(0, n_clusters, size=n_relations)

    w = (1.0 + np.arange(n_entities)) ** (-zipf_a)
    w = w[rng.permutation(n_entities)]
    w /= w.sum()

    rw = (1.0 + np.arange(n_relations)) ** (-1.0)
    rw = rw[rng.permutation(n_relations)]
    rw /= rw.sum()

    ents_by_cluster = [np.where(cluster_of == c)[0] for c in range(n_clusters)]
    csizes = np.array([e.size for e in ents_by_cluster], dtype=np.int64)
    members = np.zeros((n_clusters, max(1, int(csizes.max()))), dtype=np.int64)
    for c, e in enumerate(ents_by_cluster):
        if e.size:
            members[c, : e.size] = e

    triplets = np.empty((n_edges, 3), dtype=np.int64)
    chunk = 65536
    n_cand = 32
    for start in range(0, n_edges, chunk):
        m = min(chunk, n_edges - start)
        h = rng.choice(n_entities, size=m, p=w)
        r = rng.choice(n_relations, size=m, p=rw)
        target = latent[h] + v[r]
        target[jump[r]] = centers[tgt_cluster[r[jump[r]]]] + rng.normal(
            0, 1.0, size=(int(jump[r].sum()), latent_dim))
        d2c = ((target[:, None, :] - centers[None]) ** 2).sum(-1)
        tc = np.argmin(d2c, axis=1)
        draws = (rng.random((m, n_cand)) * csizes[tc][:, None]).astype(np.int64)
        cand = members[tc[:, None], draws]
        d = ((latent[cand] - target[:, None, :]) ** 2).sum(-1)
        t = cand[np.arange(m), np.argmin(d, axis=1)]
        triplets[start : start + m, 0] = h
        triplets[start : start + m, 1] = r
        triplets[start : start + m, 2] = t

    rng.shuffle(triplets)
    n_valid = int(n_edges * valid_frac)
    n_test = int(n_edges * test_frac)
    return Graph(
        n_entities=n_entities,
        n_relations=n_relations,
        triplets=triplets,
        train=triplets[n_valid + n_test :],
        valid=triplets[:n_valid],
        test=triplets[n_valid : n_valid + n_test],
    )
