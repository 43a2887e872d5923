#!/usr/bin/env python3
"""Run a cell traced on the chip, keep its trace, and check what the
program's scopes and spans say against the benchmark's own clock.

    python3 chipbench/tools/tracecheck.py --workload <cell> --seeds 1,2 \
        --seconds 36 --keep traces

Each run is the benchmark's ``--trace 1`` run (``harness.run_cell``),
with its trace kept under ``--keep/<seed>/`` (gzipped ``.xplane.pb``).
One JSON line per run: the result the benchmark prints, and

* ``coverage``: walker device seconds under a scope over the walker
  modules' seconds, the ``unscoped`` share, and whether the per-scope
  seconds are reported (coverage at least ``scopes.MIN_SCOPE_COVERAGE``);
* ``op_scopes``: the scopes of the walker's top ops;
* ``flush_outside_wave_ms``: the most any ``cpqx.service.flush`` span
  reaches outside the ``chipbench.wave`` span it lies in (0 when every
  flush is inside its wave);
* ``harvest_split``: the union of ``harvest.wait``, ``harvest.copy`` and
  ``harvest.retry`` inside ``engine.harvest`` over ``engine.harvest``'s
  seconds (1 when the three children cover the harvest);
* ``program_gaps``: the device's idle seconds by the innermost
  ``cpqx.*`` span open over them.

The benchmark's own runs never run this tool.
"""

from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def checks(trace_dir: str, walker_s: float) -> dict:
    from chipbench import scopes, trace

    prog = scopes.reduce_program(trace_dir)
    profile = trace.load(trace_dir)
    spans = scopes.program_spans(profile)
    waves = [(s, e) for s, e, n in trace.host_spans(profile) if n == "wave"]
    worst = 0.0
    for s, e, n in spans:
        if n != "service.flush":
            continue
        mid = (s + e) / 2
        wave = [w for w in waves if w[0] <= mid <= w[1]]
        if not wave:
            worst = float("inf")
            continue
        worst = max(worst, wave[0][0] - s, e - wave[0][1])
    harvest = [(s, e) for s, e, n in spans if n == "engine.harvest"]
    parts = [(s, e) for s, e, n in spans
             if n in ("harvest.wait", "harvest.copy", "harvest.retry")]
    covered = sum(trace.busy(parts, s, e) for s, e in harvest)
    total = sum(e - s for s, e in harvest)
    unscoped = prog["unscoped_s"]
    return {
        "coverage": {"walker_s": walker_s,
                     "walker_ops_s": prog["walker_ops_s"],
                     "scoped_s": prog["scoped_s"],
                     "scoped_share": prog["scoped_s"] / walker_s
                     if walker_s else None,
                     "unscoped_share": unscoped / walker_s
                     if walker_s else None,
                     "scopes_reported": bool(prog["scopes"])},
        "scopes": prog["scopes"],
        "op_scopes": prog["op_scopes"],
        "flush_outside_wave_ms": 1000.0 * max(worst, 0.0),
        "harvest_split": covered / total if total else None,
        "harvest_s": total,
        "program_gaps": prog["program_gaps"],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", required=True)
    args = ap.parse_args(argv)
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)

    import jax

    from chipbench import harness, trace

    cell = harness.find_cell(args.workload)
    if jax.devices()[0].platform != "tpu":
        print("tracecheck: no TPU", file=sys.stderr)
        return 1
    harness.use_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        keep = os.path.join(args.keep, str(seed))
        os.makedirs(keep, exist_ok=True)
        kept = []
        rmtree = shutil.rmtree

        def keep_trace(path, **kwargs):
            """The run's trace directory is kept past the run, then
            checked; any other directory goes as asked."""
            if os.path.basename(path).startswith("chipbench-trace-"):
                kept.append(path)
            else:
                rmtree(path, **kwargs)

        shutil.rmtree = keep_trace
        try:
            res = harness.run_cell(cell, seed, args.seconds, True,
                                   out=lambda s: print(s, file=sys.stderr))
        finally:
            shutil.rmtree = rmtree
        (trace_dir,) = kept
        walker_s = trace.reduce(trace.load(trace_dir))["walker_s"]
        line = {"workload": cell.name, "seed": seed, "result": res,
                "checks": checks(trace_dir, walker_s)}
        for f in glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                           recursive=True):
            with open(f, "rb") as src, \
                    gzip.open(os.path.join(keep, "trace.xplane.pb.gz"),
                              "wb") as dst:
                shutil.copyfileobj(src, dst)
        shutil.rmtree(trace_dir, ignore_errors=True)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
