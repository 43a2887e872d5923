#!/usr/bin/env python3
"""Run a cell several times in one process on the chip and print one JSON
line per run: the numbers ``correct`` compares, and the run's set-up and
window.  For the readings that set the limits (the control against the
program) and for sizing a configuration (the program at other sizes).

    python3 chipbench/tools/readings.py --workload <cell> --seeds 1,2,3 \
        --seconds 30 [--sides control,program] [--set config.n_vertices=3500]

The control is the plain reference with bag semantics (a join keeps the
duplicate pairs its paths give) put in the program's place for the
window's answers: the set semantics the configurations state, broken as
an optimisation that skips the dedup after a join would break it.
``--set`` overrides a key of the cell's configuration (``config.``) or
mix (``mix.``) for these runs, so the same harness path sizes a cell or
reads it on other graphs and pools (``config.graph_seed``,
``mix.query_seed``).  The benchmark's own runs never run this tool.
Executables stay in memory from one run to the next.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def override(cell, settings: list) -> None:
    """Apply ``config.<key>=<json value>`` / ``mix.<key>=...`` settings."""
    for item in settings:
        key, value = item.split("=", 1)
        part, name = key.split(".", 1)
        if part not in ("config", "mix"):
            raise ValueError(f"--set {item}: say config.<key> or mix.<key>")
        try:
            value = json.loads(value)
        except ValueError:
            pass
        setattr(cell, part, dict(getattr(cell, part), **{name: value}))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--sides", default="control")
    ap.add_argument("--set", action="append", default=[], dest="settings")
    args = ap.parse_args(argv)
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)

    import jax

    from chipbench import harness

    cell = harness.find_cell(args.workload)
    override(cell, args.settings)
    if jax.devices()[0].platform != "tpu":
        print("readings: no TPU", file=sys.stderr)
        return 1
    harness.use_compile_cache()
    sides = args.sides.split(",")
    for seed in (int(s) for s in args.seeds.split(",")):
        for side in sides:
            res = harness.run_cell(cell, seed, args.seconds, False,
                                   control=side == "control",
                                   out=lambda s: print(s, file=sys.stderr))
            print(json.dumps({"workload": cell.name, "seed": seed,
                              "side": side, "settings": args.settings,
                              "correct": res["correct"],
                              "attempted": res["attempted"],
                              "metrics": res["metrics"],
                              "memory_peak_bytes":
                                  res["device"]["memory_peak_bytes"],
                              "run": res["run"],
                              "checks": res["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
