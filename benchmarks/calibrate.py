"""Build the device cost table: micro-calibration + online refinement,
written as the versioned JSON artifact every other consumer reads
(optimizer, ``estimate_caps``, the lifecycle checkpoint codec, CI).

    PYTHONPATH=src python -m benchmarks.calibrate \
        [--smoke] [--out BENCH_costtable.json] [--json rows.json] \
        [--refine-from BENCH_*.json ...]

Stages (each emits bench rows, so the calibration itself lands in the
``BENCH_*.json`` trajectory):

1. **rungs** — the capacity rungs the engine's caps-ladder actually
   starts the gated probe templates at (``costmodel.ladder_rungs``);
2. **calibrate** — per-operator affine stage constants fitted from the
   synthetic micro-benchmarks at those rungs;
3. **refine** — end-to-end probe queries on a real engine correct the
   synthetic scale (``costmodel.refine_with_engine``), and any
   ``--refine-from`` bench JSONs from previous runs feed
   ``refine_from_trajectory`` — the loop that makes every CI run
   training data for the next one.

The table never gates correctness here — ``bench_query --cost-table``
owns the answer/plan gates; this tool only fails on calibration
breakage (no samples, unwritable output).
"""

from __future__ import annotations

import argparse
import json
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: fewest rungs/repeats that still fit")
    ap.add_argument("--out", default="BENCH_costtable.json", metavar="PATH",
                    help="where to write the DeviceCostTable JSON")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write the emitted bench rows as JSON")
    ap.add_argument("--refine-from", nargs="*", default=[], metavar="BENCH",
                    help="previous BENCH_*.json payloads whose "
                         "predicted_ns-tagged rows refine the scale")
    args, _ = ap.parse_known_args()

    from repro.core import costmodel
    from repro.core import index as cindex
    from repro.core.engine import Engine
    from repro.core.query import instantiate_template

    from benchmarks.bench_query import OPT_EXTRA, OPT_GATED, OPT_RUNG_GATED
    from benchmarks.common import DATASETS, emit, write_json

    repeats = 2 if args.smoke else 5

    g = DATASETS["skewed-hub"]()
    idx = cindex.build(g, 2)
    engine = Engine(idx)
    probes = [instantiate_template(name, labels)
              for name, labels in OPT_GATED + OPT_RUNG_GATED + OPT_EXTRA]

    rungs = costmodel.ladder_rungs(engine, probes,
                                   max_rungs=2 if args.smoke else 4)
    emit("calibrate/rungs", 0.0,
         "rungs=" + "/".join(str(r) for r in rungs))

    table = costmodel.calibrate(rungs=rungs, repeats=repeats,
                                n_vertices=g.n_vertices)
    for op in costmodel.OPERATORS:
        c = table.ops.get(op)
        if c is None:
            continue
        emit(f"calibrate/op/{op}", c.fixed_ns / 1e3,
             f"fixed_ns={c.fixed_ns:.0f};per_row_ns={c.per_row_ns:.3f};"
             f"n_samples={len(table.samples.get(op, []))}")

    scale = costmodel.refine_with_engine(table, engine, probes,
                                         repeats=repeats)
    emit("calibrate/refine/engine", 0.0,
         f"scale={scale:.4f};dispatch_floor_ns={table.dispatch_floor_ns:.0f}")

    used = 0
    payloads = []
    for path in args.refine_from:
        try:
            with open(path) as fh:
                payloads.append(json.load(fh))
        except (OSError, ValueError) as exc:
            emit("calibrate/refine/trajectory", 0.0,
                 f"SKIP;{path}={exc.__class__.__name__}")
    if payloads:
        used = table.refine_from_trajectory(payloads)
    emit("calibrate/refine/trajectory", 0.0,
         f"rows_used={used};scale={table.scale:.4f}")

    if not table.samples:
        print("FAIL: calibration produced no samples", file=sys.stderr)
        sys.exit(1)
    table.save(args.out)
    emit("calibrate/artifact", 0.0,
         f"out={args.out};device={table.device_kind};"
         f"vmem_words={table.vmem_words}")

    if args.json:
        write_json(args.json, bench="calibrate", smoke=args.smoke,
                   refined_from=len(payloads))


if __name__ == "__main__":
    main()
