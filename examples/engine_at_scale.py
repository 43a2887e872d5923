"""The paper's engine as a distributed workload: build a gMark citation
graph, shard its CPQx index over an 8-device mesh with one line —
``Engine(index, mesh=...)`` — and serve the full Fig. 5 template suite
through the sharded backend, bit-identical to the local engine.

What ``mesh=`` changes under the hood (core/sharded_index.py +
core/distributed.py): I_c2p is hash-partitioned by class so each shard
materializes only its own classes; pair-space relations live hash-
partitioned by source vertex and joins exchange rows with all_to_all
inside one shard_map; the tiny l2c/seq/cycle metadata is replicated so
class-space work (the paper's pruning) needs no communication at all.
The serving layer (QueryService) and the maintenance write path are
backend-agnostic: a flush reshards on rebind.

    PYTHONPATH=src python examples/engine_at_scale.py
(sets XLA_FLAGS itself; run as a standalone script, not under pytest)
"""

import os

if __name__ == "__main__" and "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import numpy as np  # noqa: E402

from repro import compat  # noqa: E402
from repro.core import index as cindex  # noqa: E402
from repro.core import oracle  # noqa: E402
from repro.core.distributed import ShardedBackend  # noqa: E402
from repro.core.engine import Engine  # noqa: E402
from repro.core.maintenance import MaintainableIndex  # noqa: E402
from repro.core.query import (  # noqa: E402
    TEMPLATE_ARITY,
    TEMPLATES,
    instantiate_template,
)
from repro.core.service import QueryService  # noqa: E402
from repro.data.graphs import gmark_citation  # noqa: E402


def main() -> None:
    n_shards = 8
    mesh = compat.make_mesh((n_shards,), ("engine",))
    g = gmark_citation(400, avg_degree=6, seed=0)
    idx = cindex.build(g, 2)
    print(f"graph {g}; CPQx: {idx.n_classes} classes, {idx.n_pairs} pairs")

    # the one-line scale-out: same Engine API, sharded execution
    local = Engine(idx)
    sharded = Engine(idx, mesh=mesh)
    assert isinstance(sharded.backend, ShardedBackend)
    counts = np.asarray(sharded.backend.sharded.c2p_counts)
    print(f"I_c2p class-sharded over {n_shards} devices: "
          f"{counts.tolist()} rows per shard")

    # full template suite: sharded == local (bit-identical) == oracle
    rng = np.random.default_rng(0)
    present = np.unique(g.lbl)
    for name in sorted(TEMPLATES):
        q = instantiate_template(
            name, rng.choice(present, TEMPLATE_ARITY[name]).tolist())
        a, b = local.execute(q), sharded.execute(q)
        assert a.shape == b.shape and bool(np.all(a == b)), name
        print(f"  {name:>3}: {a.shape[0]:5d} pairs — sharded == local")

    # a conjunction checked against the semantics ground truth
    q = instantiate_template("S", [0, 0, 1, 0])
    got = sorted(tuple(r) for r in sharded.execute(q).tolist())
    gt = sorted(oracle.cpq_eval(g, q))
    print(f"distributed conjunction: {len(got)} pairs; "
          f"matches semantics oracle: {got == gt}")
    assert got == gt

    # the serving + maintenance stack is backend-agnostic: queue queries,
    # apply live updates; the flush reshards the index on rebind
    mi = MaintainableIndex.build(g, 2)
    svc = QueryService(Engine(mi.flush(), mesh=mesh), maintainer=mi)
    before = svc.query(q)
    svc.apply_updates([("insert_edge", 1, 2, 0), ("insert_edge", 2, 3, 1)])
    after = svc.query(q)  # drains the write, flushes, reshards
    assert {tuple(r) for r in after.tolist()} == oracle.cpq_eval(mi.g, q)
    print(f"live updates through the sharded service: {before.shape[0]} -> "
          f"{after.shape[0]} pairs, {svc.stats.update_batches} flush "
          f"(resharded on rebind)")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
