"""Plain reference: CPQ semantics over the edge list, in numpy.

The semantics of the repository's ``oracle.cpq_eval``, kept with the
benchmark and importing nothing of the program: a relation is the sorted
array of its distinct pairs, each pair ``(v, u)`` held as the key
``v * n + u``.  An edge label selects its edges, a join composes two
relations, a conjunction intersects them, and the identity is every
``(v, v)``.  Labels are closure ids: ``l + n_labels`` is the inverse of
``l``, and every edge ``(v, u, l)`` brings ``(u, v, inverse(l))``.

Queries are the benchmark's own trees (``traffic.py``): ``("id",)``,
``("edge", label)``, ``("join", a, b)`` and ``("conj", a, b)``.
"""

from __future__ import annotations

import numpy as np


class Graph:
    """The inverse closure of a base edge list, split by label."""

    def __init__(self, n_vertices: int, n_labels: int, edges: np.ndarray):
        e = np.asarray(edges, np.int64).reshape(-1, 3)
        inv = np.stack([e[:, 1], e[:, 0], e[:, 2] + n_labels], 1)
        closed = np.concatenate([e, inv])
        self.n = int(n_vertices)
        self.n_labels = int(n_labels)
        keys = closed[:, 0] * self.n + closed[:, 1]
        self.by_label = {
            lbl: np.unique(keys[closed[:, 2] == lbl])
            for lbl in range(2 * self.n_labels)}

    def edge(self, lbl: int) -> np.ndarray:
        return self.by_label.get(int(lbl), np.zeros(0, np.int64))


def join(a: np.ndarray, b: np.ndarray, n: int, dedup: bool = True
         ) -> np.ndarray:
    """``{(v, y) : (v, u) in a, (u, y) in b}``.  ``b`` is sorted by its
    key, hence by its source, so each ``u`` finds its run by search."""
    av, au = np.divmod(a, n)
    bx, by = np.divmod(b, n)
    lo = np.searchsorted(bx, au, "left")
    cnt = np.searchsorted(bx, au, "right") - lo
    total = int(cnt.sum())
    if total == 0:
        return np.zeros(0, np.int64)
    row = np.repeat(np.arange(a.size), cnt)
    offset = np.arange(total) - np.repeat(np.cumsum(cnt) - cnt, cnt)
    out = av[row] * n + by[lo[row] + offset]
    return np.unique(out) if dedup else np.sort(out)


def evaluate(g: Graph, q: tuple, dedup: bool = True,
             memo: dict | None = None) -> np.ndarray:
    """Sorted keys of the query's answer.  ``dedup=False`` keeps the
    duplicates a join produces (bag semantics): the control that breaks
    the set semantics the configurations state.  ``memo`` keeps the
    answers of nodes over two edges, for callers that evaluate many
    queries on one graph."""
    if memo is not None and len(q) == 3 and q[1][0] == q[2][0] == "edge":
        if q not in memo:
            memo[q] = evaluate(g, q, dedup)
        return memo[q]
    op = q[0]
    if op == "edge":
        return g.edge(q[1])
    if op == "id":
        return np.arange(g.n, dtype=np.int64) * (g.n + 1)
    if op == "join":
        return join(evaluate(g, q[1], dedup, memo),
                    evaluate(g, q[2], dedup, memo), g.n, dedup)
    if op == "conj":
        a = evaluate(g, q[1], dedup, memo)
        b = evaluate(g, q[2], dedup, memo)
        if q[2] == ("id",):
            return a[a // g.n == a % g.n]
        if q[1] == ("id",):
            return b[b // g.n == b % g.n]
        if dedup:
            return np.intersect1d(a, b, assume_unique=True)
        return a[np.isin(a, b)]
    raise ValueError(f"not a query node: {q!r}")


def answer_keys(rows: np.ndarray, n: int) -> np.ndarray:
    """An (m, 2) answer as keys, in the order given."""
    rows = np.asarray(rows, np.int64).reshape(-1, 2)
    return rows[:, 0] * n + rows[:, 1]


def same_answer(rows, expected: np.ndarray, n: int) -> bool:
    """Whether ``rows`` holds exactly the pairs of ``expected``, each once."""
    if rows is None:
        return False
    got = answer_keys(rows, n)
    return got.size == expected.size and np.array_equal(np.sort(got),
                                                        expected)


def sequences(g: Graph, k: int) -> dict:
    """Every label sequence of length 1..k with at least one path, with
    the sorted keys of the pairs it connects."""
    out = {(lbl,): keys for lbl, keys in g.by_label.items() if keys.size}
    frontier = dict(out)
    for _ in range(k - 1):
        nxt = {}
        for seq, keys in frontier.items():
            for lbl, ekeys in g.by_label.items():
                if ekeys.size:
                    j = join(keys, ekeys, g.n)
                    if j.size:
                        nxt[seq + (lbl,)] = j
        out.update(nxt)
        frontier = nxt
    return out


def index_mismatches(g: Graph, k: int, seq_pairs: dict) -> list:
    """Label sequences whose pair set in the index (``seq_pairs``: sequence
    -> sorted keys, read back from the device) differs from the paths of
    the graph, including sequences one side lacks."""
    ref = sequences(g, k)
    bad = []
    for seq in sorted(set(ref) | set(seq_pairs)):
        got = seq_pairs.get(seq, np.zeros(0, np.int64))
        want = ref.get(seq, np.zeros(0, np.int64))
        if got.size != want.size or not np.array_equal(got, want):
            bad.append(seq)
    return bad
