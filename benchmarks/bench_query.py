"""Paper Fig. 6 (query time per template x method) + the PR 4 optimizer
gate (cost-based vs syntactic plans on a label-skewed graph).

Fig. 6 methods: CPQx (device engine), iaCPQx, Path [14], iaPath, BFS
(index-free host evaluation).  Datasets are CPU-scaled members of the
paper's generator families; the claim under reproduction is the
*ordering* and the orders-of-magnitude conjunction gap, not absolute
wall times.

The optimizer section runs every probe query through two engines bound
to the same index — ``Engine(idx, optimize=False)`` (the syntactic
``plan_query`` + stats-free capacity estimate) and the default
cost-based engine — and *gates on answers*: optimized == syntactic ==
numpy oracle, else FAIL and a non-zero exit.  In ``--smoke`` (CI) mode
it also requires >= 2 of the gated Fig. 5 templates to speed up >= 2x
at BOTH n_shards=1 and n_shards=8 (8 fake XLA devices, set before the
first jax import; run standalone, not under pytest).

    PYTHONPATH=src python -m benchmarks.bench_query [--smoke] [--json out.json]
"""

from __future__ import annotations

import argparse
import os
import sys

import numpy as np

QUERY_DATASETS = ["robots-like", "gmark-small"]
N_PER_TEMPLATE = 3

# Optimizer probes on the skewed-hub graph (label 0 = dense hub, 1..5
# rare).  The gated four are conjunction-heavy Fig. 5 templates whose
# answers track their *smallest* conjunct — where stats-blind planning
# hurts most.  C4 is the ROADMAP's skewed-fanout chain: its answer far
# exceeds the uniform join estimate, so before the PR 5 endpoint
# statistics its caps laddered every call — it is PASS-gated on
# *estimator health* (answers == oracle AND zero retry rungs), not on
# the >= 2x bar (at CI scale its wall-clock is dispatch-bound).  C2i
# documents identity-closure behavior ungated.
OPT_GATED = [
    ("T", [0, 0, 1]),  # (hub.hub) & rare
    ("S", [0, 0, 2, 3]),  # (hub.hub) & (rare.rare)
    ("St", [0, 4, 5]),  # hub & rare & rare  (parallel edges)
    ("TT", [0, 0, 0, 0, 1]),  # two hub triangles glued on a rare edge
]
OPT_RUNG_GATED = [
    ("C4", [1, 0, 2, 3]),  # skewed-fanout chain: join_cap estimate gate
]
OPT_EXTRA = [
    ("C2i", [0, 1]),  # (hub.rare) & id
]


def interests_for(g, k=2, n=6, seed=0):
    """Interest set = the 2-sequences realized by the benchmark queries
    (the paper uses the query workload's sequences as interests)."""
    rng = np.random.default_rng(seed)
    present = np.unique(g.lbl)
    return [tuple(rng.choice(present, 2)) for _ in range(n)]


def fig6_section() -> None:
    import jax

    from repro.core import baselines, interest, oracle
    from repro.core import index as cindex
    from repro.core.baselines import PathEngine
    from repro.core.engine import Engine
    from repro.data.graphs import random_queries_for_graph

    from benchmarks.common import DATASETS, TEMPLATE_NAMES, emit, timeit

    for ds in QUERY_DATASETS:
        g = DATASETS[ds]()
        ints = interests_for(g)
        methods = {
            "CPQx": Engine(cindex.build(g, 2)),
            "iaCPQx": Engine(interest.build_interest(g, 2, ints)),
            "Path": PathEngine(baselines.build_path(g, 2)),
            "iaPath": PathEngine(baselines.build_path(g, 2, interests=ints)),
        }
        queries = random_queries_for_graph(g, TEMPLATE_NAMES,
                                           N_PER_TEMPLATE, seed=7)
        for template in TEMPLATE_NAMES:
            qs = [q for name, q in queries if name == template]
            for mname, engine in methods.items():
                us = timeit(lambda: [engine.execute(q) for q in qs]) / len(qs)
                emit(f"fig6/{ds}/{template}/{mname}", us,
                     f"n_queries={len(qs)}")
            # index-free BFS baseline (host semantics walk)
            us = timeit(lambda: [oracle.bfs_eval(g, q) for q in qs],
                        warmup=0, iters=1) / len(qs)
            emit(f"fig6/{ds}/{template}/BFS", us, f"n_queries={len(qs)}")
        # answers agree across all methods (correctness gate of the bench)
        for name, q in queries[:6]:
            gt = oracle.cpq_eval(g, q)
            for mname, engine in methods.items():
                got = {tuple(r) for r in engine.execute(q).tolist()}
                assert got == gt, (ds, name, mname)
        jax.clear_caches()


def optimizer_section(shard_counts, iters: int, gate_speedup: bool = True) -> bool:
    """Optimized vs syntactic plans, same index, answers oracle-gated.
    Returns True when anything failed: wrong answers always fail; the
    >= 2x bar (two gated templates, every requested shard count) only
    fails when ``gate_speedup`` — the CI --smoke acceptance; full local
    runs report speedups without hard-failing on machine noise."""
    import jax

    from repro import compat
    from repro.core import index as cindex, oracle
    from repro.core.engine import Engine
    from repro.core.query import instantiate_template

    from benchmarks.common import DATASETS, emit, timeit

    g = DATASETS["skewed-hub"]()
    idx = cindex.build(g, 2)
    probes = [(name, instantiate_template(name, labels))
              for name, labels in OPT_GATED + OPT_RUNG_GATED + OPT_EXTRA]
    truth = {name: oracle.cpq_eval(g, q) for name, q in probes}

    failed = False
    for n_shards in shard_counts:
        if n_shards > 1 and jax.device_count() < n_shards:
            # a skipped leg counts as a failure when the speedup gate is
            # on: CI must never report the 8-shard acceptance green
            # without having run it
            emit(f"optimizer/skewed-hub/shards{n_shards}/acceptance", 0.0,
                 f"SKIP;only {jax.device_count()} devices"
                 + (";FAIL" if gate_speedup else ""))
            failed |= gate_speedup
            continue
        if n_shards == 1:
            e_syn = Engine(idx, optimize=False)
            e_opt = Engine(idx)
        else:
            mesh = compat.make_mesh((n_shards,), ("engine",))
            e_syn = Engine(idx, mesh=mesh, optimize=False)
            e_opt = Engine(idx, mesh=mesh)
        wins = 0
        for i, (name, q) in enumerate(probes):
            rungs0 = e_opt.telemetry.retry_rungs
            syn_rows = e_syn.execute(q)
            opt_rows = e_opt.execute(q)
            rungs_opt = e_opt.telemetry.retry_rungs - rungs0
            ok = (syn_rows.shape == opt_rows.shape
                  and bool(np.all(syn_rows == opt_rows))
                  and {tuple(r) for r in opt_rows.tolist()} == truth[name])
            us_syn = timeit(lambda: e_syn.execute(q), iters=iters)
            us_opt = timeit(lambda: e_opt.execute(q), iters=iters)
            speedup = us_syn / max(us_opt, 1e-9)
            gated = i < len(OPT_GATED)
            rung_gated = len(OPT_GATED) <= i < len(OPT_GATED) + len(
                OPT_RUNG_GATED)
            if gated and ok and speedup >= 2.0:
                wins += 1
            if rung_gated:
                # estimator-health gate: the endpoint/fanout statistics
                # must size join_cap so this skewed-fanout chain never
                # ladders (it did, every call, under the uniform estimate)
                est_ok = ok and rungs_opt == 0
                failed |= gate_speedup and not est_ok
                tag = f";estimator={'PASS' if est_ok else 'FAIL'}"
            else:
                tag = "" if gated else ";ungated"
            emit(f"optimizer/skewed-hub/shards{n_shards}/{name}", us_opt,
                 f"syntactic_us={us_syn:.1f};speedup={speedup:.2f}x;"
                 f"rungs={rungs_opt};n_rows={len(truth[name])};"
                 f"answers={'PASS' if ok else 'FAIL'}" + tag)
            failed |= not ok
        verdict = "PASS" if (wins >= 2 and not failed) else "FAIL"
        emit(f"optimizer/skewed-hub/shards{n_shards}/acceptance", 0.0,
             f"ge2x_wins={wins}/{len(OPT_GATED)};"
             f"answers==syntactic==oracle;{verdict}")
        failed |= gate_speedup and wins < 2
        del e_syn, e_opt
        jax.clear_caches()
    return failed


def calibrated_section(table_path: str, iters: int) -> bool:
    """The PR 8 cost-model gate: plans priced through a calibrated
    :class:`DeviceCostTable` vs the row-count planner vs syntactic.

    Gates (any failure returns True):

    * every calibrated plan is answer-identical to the syntactic planner
      and the numpy oracle (a mispriced table may only change plan
      choice/capacities, never answers);
    * the C4 chain — whose 3-leaf row-optimal split loses 0.3–0.6x to
      per-stage dispatch overhead at CI scale — is >= 1x vs the 2-leaf
      syntactic plan.  When the calibrated planner picks the *same* plan
      as syntactic (the expected outcome: the stage constants price the
      third dispatch out), the speedup is definitionally 1x (same jit
      executable) and the gate passes without a wall-clock coin flip.

    Every row carries ``predicted_ns`` in its derived tag —
    ``DeviceCostTable.refine_from_trajectory`` parses exactly this, so
    the emitted JSON is next run's training data.
    """
    import jax

    from repro.core import costmodel
    from repro.core import index as cindex, oracle
    from repro.core.engine import Engine
    from repro.core.optimizer import estimate_plan
    from repro.core.query import freeze_plan, instantiate_template

    from benchmarks.common import DATASETS, emit, timeit

    table = costmodel.DeviceCostTable.load(table_path)
    costmodel.activate(table)  # the VMEM ceiling for kernels
    g = DATASETS["skewed-hub"]()
    idx = cindex.build(g, 2)
    probes = [(name, instantiate_template(name, labels))
              for name, labels in OPT_GATED + OPT_RUNG_GATED + OPT_EXTRA]
    truth = {name: oracle.cpq_eval(g, q) for name, q in probes}

    e_syn = Engine(idx, optimize=False)
    e_cal = Engine(idx, cost_table=table)
    failed = False
    for name, q in probes:
        syn_rows = e_syn.execute(q)
        cal_rows = e_cal.execute(q)
        ok = (syn_rows.shape == cal_rows.shape
              and bool(np.all(syn_rows == cal_rows))
              and {tuple(r) for r in cal_rows.tolist()} == truth[name])
        failed |= not ok
        plan_cal = e_cal.plan(q)
        plans_equal = freeze_plan(plan_cal) == freeze_plan(e_syn.plan(q))
        predicted = estimate_plan(plan_cal, e_cal.stats,
                                  cost_table=table).cost_ns
        us_syn = timeit(lambda: e_syn.execute(q), iters=iters)
        us_cal = timeit(lambda: e_cal.execute(q), iters=iters)
        speedup = 1.0 if plans_equal else us_syn / max(us_cal, 1e-9)
        if name == "C4":
            c4_ok = ok and (plans_equal or speedup >= 1.0)
            failed |= not c4_ok
            tag = f";c4_gate={'PASS' if c4_ok else 'FAIL'}"
        else:
            tag = ""
        emit(f"calibrated/skewed-hub/{name}", us_cal,
             f"syntactic_us={us_syn:.1f};speedup={speedup:.2f}x;"
             f"plans_equal={plans_equal};predicted_ns={predicted:.0f};"
             f"scale={table.scale:.3f};"
             f"answers={'PASS' if ok else 'FAIL'}" + tag)
    emit("calibrated/skewed-hub/acceptance", 0.0,
         f"answers==syntactic==oracle;"
         f"{'FAIL' if failed else 'PASS'}")
    costmodel.activate(None)
    del e_syn, e_cal
    jax.clear_caches()
    return failed


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: optimizer gate only, n_shards in {1, 8}")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write the emitted rows as JSON")
    ap.add_argument("--cost-table", default=None, metavar="PATH",
                    help="run the calibrated-planner gate against this "
                         "DeviceCostTable JSON (benchmarks.calibrate "
                         "writes one)")
    args, _ = ap.parse_known_args()

    if args.smoke and "XLA_FLAGS" not in os.environ:
        # must precede the first jax import (the 8-shard leg)
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

    if not args.smoke:
        fig6_section()
    failed = optimizer_section([1, 8] if args.smoke else [1],
                               iters=2 if args.smoke else 3,
                               gate_speedup=args.smoke)
    if args.cost_table:
        failed |= calibrated_section(args.cost_table,
                                     iters=2 if args.smoke else 3)
    if args.json:
        from benchmarks.common import write_json

        write_json(args.json, bench="bench_query", smoke=args.smoke,
                   cost_table=bool(args.cost_table))
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
