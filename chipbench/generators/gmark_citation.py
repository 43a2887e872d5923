"""gMark-style citation schema (the paper's Sec. VI scalability graphs).

A copy of the repository's ``data.graphs.gmark_citation`` kept with the
benchmark so that a program change cannot move the data: three vertex
roles (80% researchers, 15% venues, 5% cities) and six labels, cites,
supervises, livesIn, worksIn, publishesIn and heldIn, each between its
role pair, with Zipf-weighted targets for cites and publishesIn."""

from __future__ import annotations

import numpy as np

LABELS = ("cites", "supervises", "livesIn", "worksIn", "publishesIn",
          "heldIn")


def generate(cfg: dict, rng: np.random.Generator):
    n_vertices = int(cfg["n_vertices"])
    n_res = int(n_vertices * 0.80)
    n_ven = int(n_vertices * 0.15)
    res = np.arange(n_res)
    ven = np.arange(n_res, n_res + n_ven)
    city = np.arange(n_res + n_ven, n_vertices)
    m = int(n_vertices * float(cfg["avg_degree"]) / 2)

    def pick(pool, size, zipf=False):
        if zipf:
            w = 1.0 / (np.arange(1, len(pool) + 1) ** 0.8)
            w /= w.sum()
            return rng.choice(pool, size=size, p=w)
        return rng.choice(pool, size=size)

    # (share of edges, source pool, target pool, Zipf targets) per label
    spec = ((0.45, res, res, True), (0.1, res, res, False),
            (0.1, res, city, False), (0.1, res, city, False),
            (0.2, res, ven, True), (0.05, ven, city, False))
    edges = []
    for lbl, (share, src, dst, zipf) in enumerate(spec):
        k = max(1, int(m * share)) if lbl == 5 else int(m * share)
        edges.append(np.stack([pick(src, k), pick(dst, k, zipf=zipf),
                               np.full(k, lbl)], 1))
    return n_vertices, len(LABELS), np.concatenate(edges, 0).astype(np.int64)
