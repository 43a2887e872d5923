"""Serving CPQ traffic: the PR-1 query serving layer end to end.

Builds CPQx over a gMark citation graph, then drives a synthetic query
workload (repeating Fig. 5 templates with a skewed label distribution —
the recurring-traffic shape a production endpoint sees) through the
three execution paths and prints the throughput and cache behavior:

  1. sequential ``Engine.execute``          (one dispatch per query)
  2. ``Engine.execute_batch``               (plan-shape bucketed vmap)
  3. ``QueryService``                       (queue + dedup + LRU cache)

Ends with live graph updates through the service write path
(``service.apply_updates`` -> coalesced mirror surgery -> mirror→device
flush -> rebind) showing epoch-keyed cache invalidation and
update→queryable latency without a rebuild — and with the PR-5
adaptation loop: an interest-aware service that starts with NO mined
interests, watches the same traffic, and indexes its hot label
sequences by itself.

    PYTHONPATH=src python examples/serve_cpq.py
"""

import time

import numpy as np

from repro.core import index as cindex
from repro.core import oracle
from repro.core.engine import Engine
from repro.core.maintenance import MaintainableIndex
from repro.core.query import TEMPLATE_ARITY, instantiate_template
from repro.core.service import QueryService
from repro.data.graphs import gmark_citation


def make_workload(g, n_queries: int, seed: int = 0):
    """Skewed recurring traffic: few templates, zipf-ish label reuse."""
    rng = np.random.default_rng(seed)
    present = np.unique(g.lbl)
    names = ["T", "C2", "S", "C2i"]
    out = []
    for _ in range(n_queries):
        name = names[int(rng.integers(0, len(names)))]
        # draw from a small label pool so queries repeat (cacheable)
        pool = present[: max(2, len(present) // 2)]
        labels = pool[rng.integers(0, len(pool), TEMPLATE_ARITY[name])]
        out.append(instantiate_template(name, labels.tolist()))
    return out


def main() -> None:
    g = gmark_citation(400, avg_degree=6, seed=0)
    idx = cindex.build(g, 2)
    engine = Engine(idx)
    print(f"graph {g}; CPQx: {idx.n_classes} classes, {idx.n_pairs} pairs")

    workload = make_workload(g, 64)

    # warm each path's executables once (compile time is not serving
    # time; note the vmapped jit keys include the batch size, so every
    # path compiles its own variants)
    for q in workload:
        engine.execute(q)
    engine.execute_batch(workload)
    warmup_svc = QueryService(engine, max_batch=32)
    for q in workload:
        warmup_svc.submit(q)
    warmup_svc.flush()

    t0 = time.perf_counter()
    seq = [engine.execute(q) for q in workload]
    t_seq = time.perf_counter() - t0

    t0 = time.perf_counter()
    bat = engine.execute_batch(workload)
    t_bat = time.perf_counter() - t0
    assert all(a.shape == b.shape and np.all(a == b)
               for a, b in zip(seq, bat))

    svc = QueryService(engine, max_batch=32)
    t0 = time.perf_counter()
    for q in workload:
        svc.submit(q)
    svc.flush()
    t_svc = time.perf_counter() - t0

    n = len(workload)
    print(f"sequential : {n / t_seq:8.0f} q/s")
    print(f"batched    : {n / t_bat:8.0f} q/s ({t_seq / t_bat:.2f}x)")
    print(f"service    : {n / t_svc:8.0f} q/s cold "
          f"(dedup folded {svc.stats.deduped} of {n})")

    t0 = time.perf_counter()
    for q in workload:
        svc.submit(q)
    svc.flush()
    t_warm = time.perf_counter() - t0
    print(f"service    : {n / t_warm:8.0f} q/s warm "
          f"({svc.stats.cache_hits} cache hits)")

    # live updates through the write path: apply_updates queues writes
    # and bumps the epoch (O(1) invalidation of every cached answer);
    # the next query drain coalesces them into one mirror batch + one
    # mirror→device flush — no rebuild on the serving path
    m = MaintainableIndex.build(g, 2)
    svc = QueryService(engine, max_batch=32, maintainer=m)
    q = workload[0]
    svc.query(q)  # warm the cache at the current epoch
    v, u, l = map(int, m.g._base_edges()[0])
    t0 = time.perf_counter()
    svc.apply_updates([("insert_edge", u, v, l)])  # reciprocal edge
    svc.apply_updates([("delete_edge", v, u, l)])
    req = svc.submit(q)
    print(f"after 2 writes: epoch={svc.graph_epoch}, served from cache: "
          f"{req.from_cache}, queued updates: {svc.pending_updates}")
    if not req.done:
        svc.flush()  # drains the coalesced writes, then answers
    t_upd = time.perf_counter() - t0
    assert {tuple(r) for r in req.result.tolist()} == oracle.cpq_eval(m.g, q)
    print(f"post-update answer verified against the semantics oracle "
          f"(update->queryable {t_upd * 1e3:.1f} ms, "
          f"{svc.stats.update_batches} coalesced maintenance round)")

    # adaptive iaCPQx: start from an interest-aware index with nothing
    # mined, let the workload sketch + benefit model + controller close
    # the loop (proposals drain through the same write path as above)
    from repro.core.workload import AdaptationConfig, AdaptationController

    mi = MaintainableIndex.build(g, 2, interests=[])
    adaptive = QueryService(
        Engine(mi.flush()), maintainer=mi,
        adapter=AdaptationController(2, config=AdaptationConfig(budget=4)),
        adapt_interval=32, max_batch=32)
    for _ in range(3):  # recurring traffic: the frequency signal
        for q in workload:
            adaptive.submit(q)
        adaptive.flush()
    mined = sorted(s for s in mi.index.interests if len(s) >= 2)
    q = workload[0]
    assert {tuple(r) for r in adaptive.query(q).tolist()} == \
        oracle.cpq_eval(mi.g, q)
    print(f"adaptive   : mined interests {mined} "
          f"({adaptive.stats.adapt_rounds} rounds, "
          f"{adaptive.stats.sequences_observed} sequence votes, "
          f"answers oracle-verified)")

    # kill and promote: checkpoint the adaptive service (drains writes,
    # snapshots arrays + mirror + mined interests + sketch at one
    # epoch), "crash", and promote a cold replica from the last
    # committed step — load + rebind, no rebuild, caches invalidated by
    # the epoch bump, the mined interest set already hot
    import tempfile

    from repro.core import lifecycle

    with tempfile.TemporaryDirectory() as ckpt_dir:
        # steady state: one more serving pass at the post-mining index
        # generation, so its executables are compiled (the replica hits
        # the same jit cache — promotion measures recovery, not XLA)
        adaptive.bump_epoch()
        adaptive.query(q)
        adaptive.checkpoint(ckpt_dir)
        del adaptive  # the crash: in-process serving state is gone

        t0 = time.perf_counter()
        replica = lifecycle.restore_service(ckpt_dir)
        first = replica.query(q)  # first served answer after the crash
        t_promote = time.perf_counter() - t0
        assert {tuple(r) for r in first.tolist()} == \
            oracle.cpq_eval(replica.maintainer.g, q)
        assert sorted(s for s in replica.maintainer.index.interests
                      if len(s) >= 2) == mined
        print(f"promotion  : replica serving in {t_promote * 1e3:.1f} ms "
              f"(epoch={replica.graph_epoch}, interests intact, first "
              f"answer oracle-verified)")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
