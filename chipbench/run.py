#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is found by name in ``BENCHMARK.json`` at the root of the
checkout.  The run builds the cell's graph and index from ``--seed``,
warms every executable its traffic uses (set-up), serves whole waves for
``--seconds`` seconds (the window), then checks every answer of the
window, and the index, against the plain reference.  With ``--trace 0``
the result holds the cell's end-to-end metrics; with ``--trace 1`` its
per-layer metrics, read from a profiler trace of the window's first pass
over the pool, and a breakdown of device ops and idle gaps.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, ``breakdown`` when
traced, ``checks``); the last lines of standard error give each number
compared with its limit.  Where JAX finds no TPU, or fewer chips than
the cell asks for, the run exits non-zero and prints no result.  JAX's
persistent compilation cache is ``$JAX_COMPILATION_CACHE_DIR`` when set,
else ``.jax_cache`` at the root of the checkout.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    from chipbench import harness

    cell = harness.find_cell(args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"chipbench: cell {cell.name} needs {cell.chips} TPU chip(s); "
              f"JAX found {len(devices)} {devices[0].platform} device(s). "
              "Nothing was run.", file=sys.stderr)
        return 1
    harness.use_compile_cache()

    def out(line):
        print(line, file=sys.stderr, flush=True)

    result = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                              t_start=T_START, out=out)
    print(json.dumps(result), flush=True)
    for name, c in result["checks"].items():
        out(f"check {name}: {c['value']} (limit {c['limit']})")
    out(f"correct: {result['correct']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
