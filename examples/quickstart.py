"""Quickstart: the paper's running example, end to end.

Builds CPQx over the Fig.-1 social graph, runs the triad query
ff ∩ f⁻ (people and their followers in a 3-cycle), and shows the
class-space pruning that makes it fast — all on the public API.

    PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np

from repro.core import index as cindex
from repro.core import interest, oracle
from repro.core.engine import Engine
from repro.core.graph import example_graph
from repro.core.query import parse

NAMES = ["sue", "joe", "zoe", "tim", "ada", "tom", "bob", "kim",
         "amy", "ben", "eva", "max", "blog123", "blog987"]


def main() -> None:
    g = example_graph()
    print(f"graph: {g}")

    # 1. build the CPQ-aware index (k = 2, the paper's default)
    idx = cindex.build(g, k=2)
    l2c, c2p = idx.size_entries()
    print(f"CPQx built: {idx.n_classes} equivalence classes over "
          f"{idx.n_pairs} s-t pairs (|I_l2c|={l2c}, |I_c2p|={c2p})")

    # 2. the paper's query: conjunction of ff and f⁻ (Sec. I)
    q = parse("(f . f) & f-", {"f": 0, "v": 1}, g.n_labels)
    engine = Engine(idx)
    answers = engine.execute(q)
    print(f"\n⟦ff ∩ f⁻⟧ = {[(NAMES[v], NAMES[u]) for v, u in answers]}")

    # 3. why it was fast: the conjunction ran on class ids (Prop. 4.1)
    ff = set(np.asarray(idx.arrays.l2c_cls)[slice(*idx.lookup_range((0, 0)))].tolist())
    fi = set(np.asarray(idx.arrays.l2c_cls)[slice(*idx.lookup_range((2,)))].tolist())
    print(f"lookup(ff) -> classes {sorted(ff)}; lookup(f⁻) -> {sorted(fi)}; "
          f"intersection {sorted(ff & fi)} — one class holds every answer")

    # 4. ground truth check against the denotational semantics
    assert {tuple(r) for r in answers.tolist()} == oracle.cpq_eval(g, q)
    print("matches the CPQ semantics oracle ✓")

    # 5. the interest-aware variant: tiny index, same answers
    ia = interest.build_interest(g, 2, interests=[(0, 0)])
    got = {tuple(r) for r in Engine(ia).execute(q).tolist()}
    print(f"\niaCPQx (interest = {{ff}}): {ia.n_classes} classes "
          f"(vs {idx.n_classes}); same answers: "
          f"{got == oracle.cpq_eval(g, q)}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
