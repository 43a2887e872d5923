#!/usr/bin/env python3
"""Serve CPQ queries end to end on a TPU and check every answer.

    python3 chip_smoke.py [--seed N]               # one chip
    python3 chip_smoke.py --chips 4 [--seed N]     # the sharded engine

One chip: generate the gMark citation graph of the paper's scalability
suite (Sec. VI) at ``N_VERTICES`` vertices, build CPQx at k = 2 on the
device, wrap it in ``Engine`` and ``QueryService`` with their default
options, serve the paper's Fig. 5 templates through ``submit``/``flush``
and check every answer against the numpy oracle.  The queries are served
twice, each time by a fresh service: the first pass compiles, the second
is the serving time, with no compile inside it.

Four chips: at ``FOUR_CHIP_VERTICES`` vertices, shard the index over a
four-device mesh, serve one query of each template through
``Engine(idx, mesh=mesh)`` and a ``QueryService``, and check the answers
bit-identical to the one-chip engine and to the oracle.

Earlier lines report sizes and times.  The last line is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``.  Where JAX
finds no TPU, or the repository is not next to the script, it exits
non-zero before any phase runs and prints no result; a failed phase or a
wrong answer exits non-zero too.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import threading
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

#: The deployment: gMark citation schema, k = 2 (the paper's default).
#: 20,000 vertices.  The one run on one TPU v5e at 40,000 ended at 1155 s
#: of its 1300 s with the device out of memory, which the overflow ladder
#: then caused (it grew every capacity where one was short); that run was
#: not repeated.  Its phases (build 167 s, the service's executables
#: compiled by 335 s) and this size's (about 555 s for the whole run)
#: put 40,000 near the 1200 s a smoke run may take, so the size is 20,000.
N_VERTICES = 20_000
#: The four-chip phase's graph, same schema and degree, half the size: it
#: builds the index, then serves every query twice (one chip, four chips)
#: while holding four chips, and at this size it should end in about
#: five minutes.
FOUR_CHIP_VERTICES = 10_000
AVG_DEGREE = 8
K = 2
#: Queries drawn per Fig. 5 template; every template has a join or a
#: conjunction, so all twelve are kept.
N_PER_TEMPLATE = 3

# tracing is left out: its events nest (a jit traced inside another is
# timed inside the outer trace), lowering and compiling do not
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileClock:
    """Seconds JAX spent lowering and compiling (summed over threads), and
    the number of backend compiles, while registered (a context manager)."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self._lock = threading.Lock()

    def _listen(self, event: str, duration_secs: float, **_):
        if event in _COMPILE_EVENTS:
            with self._lock:
                self.seconds += duration_secs
                self.compiles += event == _BACKEND_COMPILE

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._listen)


@contextlib.contextmanager
def timed(report: dict, key: str):
    t0 = time.perf_counter()
    yield
    report[key] = time.perf_counter() - t0


def make_graph(n_vertices: int, seed: int):
    from repro.data.graphs import gmark_citation

    return gmark_citation(n_vertices, avg_degree=AVG_DEGREE, seed=seed)


def make_queries(g, seed: int, n_per: int):
    from benchmarks.common import TEMPLATE_NAMES
    from repro.data.graphs import random_queries_for_graph

    return random_queries_for_graph(g, TEMPLATE_NAMES, n_per, seed=seed)


def build_index(g, out=print) -> tuple:
    """CPQx at k = 2 on the default device; returns (index, report)."""
    import jax

    from repro.core import index as cindex
    from repro.core.capacity import estimate_build_caps

    rep: dict = {}
    with timed(rep, "caps_s"):
        caps = estimate_build_caps(g, K)
    with timed(rep, "build_s"), CompileClock() as clock:
        idx = cindex.build(g, K, caps)
        jax.block_until_ready(idx.arrays)
    rep["build_compile_s"] = clock.seconds
    rep["index_bytes"] = sum(int(x.nbytes) for x in jax.tree.leaves(idx.arrays))
    out(f"index: k={K} classes={idx.n_classes} pairs={idx.n_pairs} "
        f"device_bytes={rep['index_bytes']}")
    out(f"build: capacity_estimate_s={rep['caps_s']:.3f} "
        f"device_build_s={rep['build_s']:.3f} "
        f"(of which compile_s={rep['build_compile_s']:.3f})")
    return idx, rep


def oracle_answers(g, queries) -> list:
    from repro.core import oracle

    return [oracle.cpq_eval(g, q) for _, q in queries]


def serve(engine, queries) -> list:
    """One pass through a fresh ``QueryService`` (default options):
    submit every query, flush, return the answers as (n, 2) arrays."""
    from repro.core.service import QueryService

    svc = QueryService(engine)
    reqs = [svc.submit(q) for _, q in queries]
    svc.flush()
    bad = [r.rid for r in reqs if not r.done or r.result is None]
    if bad:
        raise RuntimeError(f"requests {bad} were not answered")
    return [r.result for r in reqs]


def count_matches(answers, expected) -> int:
    return sum({tuple(r) for r in a.tolist()} == e
               for a, e in zip(answers, expected))


def precompile(engines, queries, out=print) -> dict:
    """Compile, in parallel threads, every executable the first service
    round over ``queries`` dispatches on each of ``engines`` (one per
    ``Engine.group_batch`` group).  The services' own dispatches then
    find them compiled.  Returns the report, with the compiled groups of
    the first engine under "compiled"."""
    from concurrent.futures import ThreadPoolExecutor

    uniq = list(dict.fromkeys(q for _, q in queries))
    work = [(e, g) for e in engines for g in e.group_batch(uniq)]
    workers = max(1, min(len(work), (os.cpu_count() or 1) // 3))
    rep: dict = {}
    with timed(rep, "compile_s"), CompileClock() as clock, \
            ThreadPoolExecutor(workers) as pool:
        compiled = list(pool.map(
            lambda eg: eg[0].backend.compile_batch(
                eg[1].shape, eg[1].caps, eg[1].ranges), work))
    groups = [g for _, g in work]
    rep.update(executables=len(work), compile_cpu_s=clock.seconds,
               workers=workers,
               compiled=[(g, c) for (e, g), c in zip(work, compiled)
                         if e is engines[0]])
    top = max(groups, key=lambda g: (g.caps.pair_cap, g.caps.join_cap))
    out(f"compile: {len(work)} executables in compile_s="
        f"{rep['compile_s']:.3f} on {workers} threads (lowering + "
        f"compiling summed over threads {clock.seconds:.3f} s); largest "
        f"caps {top.caps}")
    return rep


def implementations(compiled) -> dict:
    """Which implementation ``conj_classes`` and ``materialize`` compiled
    to, read from the compiled program of a group whose plan conjoins two
    class lists."""
    from repro.core.backend import op_implementations

    for group, exe in compiled:
        shape = group.shape
        if shape[0] == "conj" and shape[1][0] == shape[2][0] == "lookup":
            return op_implementations(exe.as_text())
    raise RuntimeError("no plan conjoins two lookups")


def serve_one_chip(n_vertices: int, seed: int, n_per: int = N_PER_TEMPLATE,
                   out=print) -> dict:
    """The one-chip main path; returns the report it printed."""
    import jax

    from repro.core.engine import Engine

    g = make_graph(n_vertices, seed)
    out(f"graph: gmark_citation vertices={g.n_vertices} "
        f"edges={len(g.src)} (inverse closure included) seed={seed}")
    idx, rep = build_index(g, out)
    queries = make_queries(g, seed, n_per)
    with timed(rep, "oracle_s"):
        expected = oracle_answers(g, queries)
    out(f"oracle: {len(queries)} queries in {rep['oracle_s']:.3f} s")

    engine = Engine(idx)
    pre = precompile([engine], queries, out)
    rep.update(compile_s=pre["compile_s"], executables=pre["executables"])
    rep["impl"] = implementations(pre["compiled"])
    out("implementations: " + " ".join(
        f"{op}={how}" for op, how in rep["impl"].items()))
    with timed(rep, "cold_s"), CompileClock() as cold:
        cold_answers = serve(engine, queries)
    with timed(rep, "serve_s"), CompileClock() as warm:
        answers = serve(engine, queries)
    rep.update(served=len(answers), cold_compiles=cold.compiles,
               serve_compiles=warm.compiles,
               matched=count_matches(answers, expected),
               matched_cold=count_matches(cold_answers, expected),
               answer_pairs=sum(len(a) for a in answers))
    out(f"first pass: {rep['served']} queries in cold_s={rep['cold_s']:.3f} "
        f"({rep['cold_compiles']} more compiles, for overflow retries)")
    out(f"serve: {rep['served']} queries in serve_s={rep['serve_s']:.3f} "
        f"({rep['serve_compiles']} compiles in the window), "
        f"{rep['answer_pairs']} answer pairs")
    out(f"answers: matched {rep['matched']}/{rep['served']} "
        f"(first pass {rep['matched_cold']}/{rep['served']}) "
        "against oracle.cpq_eval")
    stats = jax.devices()[0].memory_stats() or {}
    rep["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    out(f"memory: peak_bytes_in_use={rep['peak_bytes_in_use']}")
    rep["ok"] = (rep["matched"] == rep["matched_cold"] == rep["served"] > 0
                 and rep["serve_compiles"] == 0
                 and "absent" not in rep["impl"].values())
    return rep


def one_per_template(queries) -> list:
    """The first query of each template, in template order."""
    seen: set = set()
    return [(name, q) for name, q in queries
            if not (name in seen or seen.add(name))]


def serve_four_chips(n_vertices: int, seed: int, n_per: int = N_PER_TEMPLATE,
                     out=print) -> dict:
    """The sharded engine over a four-device mesh against the one-chip
    engine and the oracle, one query per template; returns the report it
    printed."""
    import jax
    import numpy as np

    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro import compat
    from repro.core.engine import Engine
    from repro.core.sharded_index import index_specs

    g = make_graph(n_vertices, seed)
    out(f"graph: gmark_citation vertices={g.n_vertices} "
        f"edges={len(g.src)} seed={seed}")
    idx, rep = build_index(g, out)
    queries = one_per_template(make_queries(g, seed, n_per))
    expected = oracle_answers(g, queries)
    local_engine = Engine(idx)
    mesh = compat.make_mesh((4,), ("engine",))
    engine = Engine(idx, mesh=mesh)
    # every leaf carries its NamedSharding: a sharded leaf one shard per
    # device, a replicated leaf a full copy on each
    placed = [
        isinstance(leaf.sharding, NamedSharding) and leaf.sharding.spec == spec
        and len(leaf.sharding.device_set) == 4
        and (spec == P() or leaf.sharding.shard_shape(leaf.shape)[0] == 1)
        for leaf, spec in zip(engine.backend.sharded, index_specs("engine"))]
    rep["shards_placed"] = all(placed)
    out(f"sharding: {sum(placed)}/{len(placed)} index leaves placed by "
        f"NamedSharding over mesh {dict(mesh.shape)}, sharded leaves one "
        f"shard per device")
    pre = precompile([engine, local_engine], queries, out)
    with timed(rep, "local_s"):
        local = serve(local_engine, queries)
    with timed(rep, "serve_s"), CompileClock() as clock:
        answers = serve(engine, queries)
    rep.update(served=len(answers), compile_s=pre["compile_s"],
               matched=count_matches(answers, expected),
               matched_local=count_matches(local, expected),
               identical=sum(a.shape == b.shape and bool(np.all(a == b))
                             for a, b in zip(answers, local)))
    out(f"one chip: {rep['served']} queries in local_s={rep['local_s']:.3f}")
    out(f"serve: {rep['served']} queries in serve_s={rep['serve_s']:.3f} "
        f"on four chips ({clock.compiles} compiles, for overflow retries)")
    out(f"answers: bit-identical to one chip {rep['identical']}/"
        f"{rep['served']}, matched oracle {rep['matched']}/{rep['served']} "
        f"(one chip {rep['matched_local']}/{rep['served']})")
    for d in jax.devices():
        out(f"memory: device {d.id} peak_bytes_in_use="
            f"{(d.memory_stats() or {}).get('peak_bytes_in_use')}")
    rep["ok"] = (rep["shards_placed"]
                 and rep["identical"] == rep["matched"]
                 == rep["matched_local"] == rep["served"] > 0)
    return rep


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the graph and the query draw")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip main path; 4: only the sharded "
                         "engine on four chips against one chip")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repository next to {Path(__file__).name} "
              f"(expected {ROOT / 'src' / 'repro'})", file=sys.stderr)
        return 2
    for p in (str(ROOT), str(ROOT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)

    import jax

    from repro.compile_cache import enable_compile_cache

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (jax.devices()[0].platform="
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1
    cache_dir = enable_compile_cache()
    print(f"device: {devices[0].platform} {devices[0].device_kind} "
          f"x{len(devices)}; compile cache {cache_dir}", flush=True)

    def out(line):
        print(line, flush=True)

    if args.chips == 1:
        rep = serve_one_chip(N_VERTICES, args.seed, out=out)
    else:
        rep = serve_four_chips(FOUR_CHIP_VERTICES, args.seed, out=out)
    if not rep["ok"]:
        print("chip_smoke: FAILED (see the lines above)", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
