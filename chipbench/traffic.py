"""The one traffic generator: a mix file (``mixes/<name>.json``) in, a
pool of distinct queries cut into fixed waves out.

A mix names Fig. 5 templates, the pool size, the wave size and the seed
of the draw.  Each template is filled with labels drawn uniformly from
the closure labels the graph has (the paper's Sec. VI workload), and a
draw whose answer is empty is drawn again, so that every query asks for
something the graph holds (on a schema-typed graph most uniform label
chains connect nothing).  The pool is cut into waves in the drawn order.  The run's ``--seed`` then
only reorders: the waves, and the queries within each wave.  So every
seed serves the same queries in the same waves (the same executables and
the same work) in another order, on a graph whose vertex ids the seed
also permutes (``harness.py``).
"""

from __future__ import annotations

import numpy as np

# The paper's Fig. 5 templates over closure label ids, built as the
# repository's ``query.TEMPLATES`` builds them (joins associate left).


def _e(lbl):
    return ("edge", int(lbl))


def _j(*qs):
    out = qs[0]
    for q in qs[1:]:
        out = ("join", out, q)
    return out


def _c(a, b):
    return ("conj", a, b)


_ID = ("id",)

TEMPLATES = {
    "C2": lambda a, b: _j(_e(a), _e(b)),
    "C4": lambda a, b, c, d: _j(_e(a), _e(b), _e(c), _e(d)),
    "C2i": lambda a, b: _c(_j(_e(a), _e(b)), _ID),
    "Ti": lambda a, b, c: _c(_j(_e(a), _e(b), _e(c)), _ID),
    "Si": lambda a, b, c, d: _c(_j(_e(a), _e(b), _e(c), _e(d)), _ID),
    "T": lambda a, b, c: _c(_j(_e(a), _e(b)), _e(c)),
    "S": lambda a, b, c, d: _c(_j(_e(a), _e(b)), _j(_e(c), _e(d))),
    "TT": lambda a, b, c, d, e: _c(_c(_j(_e(a), _e(b)), _e(e)),
                                   _c(_j(_e(c), _e(d)), _e(e))),
    "St": lambda a, b, c: _c(_c(_e(a), _e(b)), _e(c)),
    "TC": lambda a, b, c, d, e: _j(_c(_j(_e(a), _e(b)), _e(c)), _e(d),
                                   _e(e)),
    "SC": lambda a, b, c, d, e, f: _j(_c(_j(_e(a), _e(b)),
                                         _j(_e(c), _e(d))), _e(e), _e(f)),
    "ST": lambda a, b, c, d, e: _j(_c(_e(a), _e(b)),
                                   _c(_j(_e(c), _e(d)), _e(e))),
}
ARITY = {name: fn.__code__.co_argcount for name, fn in TEMPLATES.items()}
MAX_DRAWS = 1_000_000


def draw_pool(mix: dict, labels_present, nonempty) -> list:
    """``mix["pool"]`` distinct ``(template, query)`` pairs: templates in
    turn, labels uniform over ``labels_present``, each kept only where
    ``nonempty(query)``, then shuffled."""
    rng = np.random.default_rng(int(mix["query_seed"]))
    labels = np.asarray(sorted(int(x) for x in labels_present))
    names = list(mix["templates"])
    pool, seen, draws = [], set(), 0
    while len(pool) < int(mix["pool"]):
        draws += 1
        if draws > MAX_DRAWS:
            raise RuntimeError(f"no pool of {mix['pool']} queries with "
                               f"answers after {MAX_DRAWS} draws")
        name = names[len(pool) % len(names)]
        q = TEMPLATES[name](*rng.choice(labels, ARITY[name]).tolist())
        if q not in seen and nonempty(q):
            seen.add(q)
            pool.append((name, q))
    order = rng.permutation(len(pool))
    return [pool[i] for i in order]


def waves(mix: dict, pool: list, rng: np.random.Generator) -> list:
    """The pool cut into waves of ``mix["wave"]``, each a list of pool
    indices; ``rng`` orders the waves and the queries inside each."""
    size = int(mix["wave"])
    if len(pool) % size:
        raise ValueError("the pool must be a whole number of waves")
    cut = [list(range(i, i + size)) for i in range(0, len(pool), size)]
    return [[cut[w][i] for i in rng.permutation(size)]
            for w in rng.permutation(len(cut))]


def to_cpq(q: tuple):
    """The program's query object for a benchmark query tree, built with
    the program's public constructors (its input interface)."""
    from repro.core.query import Conj, Edge, Identity, Join

    op = q[0]
    if op == "id":
        return Identity()
    if op == "edge":
        return Edge(q[1])
    ctor = Join if op == "join" else Conj
    return ctor(to_cpq(q[1]), to_cpq(q[2]))
