"""Benchmark driver — one bench per paper table/figure (deliverable (d)).

Prints ``name,us_per_call,derived`` CSV rows.  CPU-scaled datasets from
the same generator families as the paper's suite; correctness gates
(all methods agree with the semantics oracle) run inside each bench.
``--json`` additionally serializes every emitted row (plus platform
metadata and the failure list) — CI uploads that file as the
perf-trajectory artifact.

    PYTHONPATH=src python -m benchmarks.run [--only fig6 table4 ...]
                                           [--json out.json]
"""

from __future__ import annotations

import argparse
import sys
import time

BENCHES = {
    "fig6": "benchmarks.bench_query",  # query time per template x method
    "table3": "benchmarks.bench_pruning",  # pruning power
    "table4": "benchmarks.bench_index",  # index size + build time
    "table5": "benchmarks.bench_update",  # maintenance (+ tables 6/7)
    "fig14": "benchmarks.bench_k",  # behavior in k (+ fig 15)
    "fig11": "benchmarks.bench_scalability",  # graph-size scaling
    "kernels": "benchmarks.bench_kernels",  # Pallas vs jnp ref
    "calibrate": "benchmarks.calibrate",  # device cost table artifact (PR 8)
    "throughput": "benchmarks.bench_throughput",  # serving qps (PR 1)
    "adaptive": "benchmarks.bench_adaptive",  # drifting-workload mining (PR 5)
    "recovery": "benchmarks.bench_recovery",  # kill-and-recover TTFCA (PR 6)
    "serving": "benchmarks.bench_serving",  # multi-tenant SLO serving (PR 7)
    "rpq": "benchmarks.bench_rpq",  # RPQ fixpoints + Cypher surface (PR 9)
    "cluster": "benchmarks.bench_cluster",  # worker-process fleet (PR 10)
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", nargs="*", default=None,
                    help=f"subset of {sorted(BENCHES)}")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="serialize all emitted rows to PATH")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    todo = args.only or list(BENCHES)
    print("name,us_per_call,derived")
    t0 = time.time()
    failed = []
    for key in todo:
        mod_name = BENCHES[key]
        t1 = time.time()
        try:
            mod = __import__(mod_name, fromlist=["main"])
            mod.main()
            print(f"# {key} done in {time.time()-t1:.1f}s", file=sys.stderr)
        except SystemExit as e:  # a bench's own acceptance gate tripped
            if e.code:
                failed.append(key)
                print(f"# {key} FAILED: exit {e.code}", file=sys.stderr)
        except Exception as e:  # noqa: BLE001
            failed.append(key)
            print(f"# {key} FAILED: {type(e).__name__}: {e}", file=sys.stderr)
    elapsed = time.time() - t0
    print(f"# total {elapsed:.1f}s", file=sys.stderr)
    if args.json:
        from benchmarks.common import write_json

        write_json(args.json, benches=todo, failed=failed,
                   elapsed_s=round(elapsed, 1))
    if failed:
        raise SystemExit(f"benches failed: {failed}")


if __name__ == "__main__":
    main()
