"""One run of one cell: build, warm, measure, check, report.

Everything a cell needs is found by name.  ``BENCHMARK.json`` (at the
root of the checkout) names the cell's configuration file and traffic
mix; the configuration names its generator (``generators/<name>.py``);
each per-layer metric is read by ``metrics/<name>.py``.  A cell is added
with data files and entries only.

The served path is the program's own: ``QueryService.submit`` and
``flush`` over one long-lived ``Engine`` (optimizer plan, capacity
estimates, buckets, overflow ladder) on ``LocalBackend``.  Each wave goes
through a fresh ``QueryService`` with its default options, so no answer
comes from the service's result cache.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import shutil
import statistics
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

from . import reference, spans, trace, traffic

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: list
    per_layer: list


def use_compile_cache() -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    where set, else ``.jax_cache`` at the root of the checkout (a fixed
    path: the path is part of the cache's key).  Every executable is
    cached, however short its compile."""
    import os

    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find_cell(name: str, bench: dict | None = None,
              root: Path = ROOT) -> Cell:
    """The cell ``name`` with its configuration, mix and metrics."""
    bench = bench if bench is not None else load_benchmark(root)
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = by_name[name]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / cfg["file"]).read_text())
    mix = json.loads((HERE / "mixes" / f"{w['traffic']}.json").read_text())

    def here(m):
        return name in m.get("workloads", [name])

    e2e = [m for m in bench["end_to_end"] if here(m)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if here(m) and m["moves"] in reported]
    return Cell(name, int(w["chips"]), config, mix, e2e, per_layer)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{path.parent.name}_{path.stem}".replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def make_graph(config: dict, seed: int):
    """``(n_vertices, n_labels, edges)``: the configuration's graph, drawn
    from its own ``graph_seed``, with vertex ids permuted by ``seed``.
    Every seed thus serves an isomorphic graph: the same sizes, the same
    capacities and executables, the same work, on other ids."""
    gen = load_module(HERE / "generators" / f"{config['generator']}.py")
    n, n_labels, edges = gen.generate(
        config, np.random.default_rng(int(config["graph_seed"])))
    perm = np.random.default_rng(_seed(seed, 0)).permutation(n)
    edges = edges.copy()
    edges[:, :2] = perm[edges[:, :2]]
    return n, n_labels, edges


def _seed(seed: int, stream: int) -> np.random.SeedSequence:
    return np.random.SeedSequence([int(seed) % (1 << 63), stream])


def build_index(n: int, n_labels: int, edges, k: int, rep: dict):
    """CPQx on the device, as ``core.index.build`` makes it, with its
    program compiled (or loaded) ahead so that ``build_s`` holds no
    compile: the host capacity estimate, the edge upload, the device
    build up to ``block_until_ready`` and the sequence table pull."""
    import jax

    from repro.core import index as cindex
    from repro.core.capacity import estimate_build_caps
    from repro.core.graph import LabeledGraph
    from repro.core.paths import device_graph

    g = LabeledGraph.from_edges(n, n_labels, edges)
    t0 = time.perf_counter()
    caps = estimate_build_caps(g, k)
    rep["build_caps_s"] = time.perf_counter() - t0
    t1 = time.perf_counter()
    dg = jax.block_until_ready(device_graph(g))
    upload_s = time.perf_counter() - t1
    exe = cindex.build_index_arrays.lower(dg, k, caps.key()).compile()
    t2 = time.perf_counter()
    arrays = jax.block_until_ready(exe(dg))
    if bool(arrays.overflow):
        raise RuntimeError("index build overflow")
    idx = cindex.CPQxIndex(k=k, n_vertices=n, arrays=arrays,
                           seq_ranges=cindex._pull_seq_ranges(arrays, k),
                           caps=caps)
    rep["build_s"] = (rep["build_caps_s"] + upload_s
                      + time.perf_counter() - t2)
    rep["index_bytes"] = sum(int(x.nbytes)
                             for x in jax.tree.leaves(idx.arrays))
    rep["n_pairs"] = idx.n_pairs
    rep["n_classes"] = idx.n_classes
    return idx


def make_pool(mix: dict, n: int, n_labels: int, edges) -> list:
    """The mix's pool over this graph's closure labels; a draw whose
    answer is empty is drawn again (decided by the reference, which is
    independent of the program)."""
    labels = np.unique(np.concatenate([edges[:, 2], edges[:, 2] + n_labels]))
    g = reference.Graph(n, n_labels, edges)
    memo: dict = {}
    return traffic.draw_pool(
        mix, labels, lambda q: reference.evaluate(g, q, memo=memo).size > 0)


def index_pairs(idx) -> dict:
    """Label sequence -> sorted keys of its pairs, read back from the
    device index (I_l2c then I_c2p), duplicates kept."""
    a = idx.arrays
    n = idx.n_vertices
    count = int(a.seq_count)
    table = np.asarray(a.seq_table)[:count]
    starts = np.asarray(a.seq_starts)[:count]
    ends = np.asarray(a.seq_ends)[:count]
    l2c = np.asarray(a.l2c_cls)
    cstart = np.asarray(a.class_starts).astype(np.int64)
    keys = (np.asarray(a.c2p_v).astype(np.int64) * n
            + np.asarray(a.c2p_u).astype(np.int64))
    out = {}
    for row, s, e in zip(table, starts, ends):
        cls = l2c[s:e]
        parts = [keys[cstart[c]:cstart[c + 1]] for c in cls]
        got = np.concatenate(parts) if parts else np.zeros(0, np.int64)
        out[tuple(int(x) for x in row if x >= 0)] = np.sort(got)
    return out


class Server:
    """The system under test and the benchmark's driving of it."""

    def __init__(self, idx, pool: list, waves: list, spans_=None):
        from repro.core.engine import Engine

        self.engine = Engine(idx)
        if spans_ is not None:
            spans_.instrument(self.engine)
        self.queries = [traffic.to_cpq(q) for _, q in pool]
        self.waves = waves

    def compile_ahead(self, workers: int) -> None:
        """Compile (or load from the persistent cache) every walker
        executable of every wave, in threads.  The retry rungs are found
        only by running: set-up then serves the pool once, after which
        every executable the window meets is in memory."""
        groups = [g for wave in self.waves
                  for g in self.engine.group_batch(
                      [self.queries[i] for i in wave])]
        backend = self.engine.backend

        def compile_group(g):
            return backend.compile_batch(g.shape, g.caps, g.ranges)

        with ThreadPoolExecutor(max(1, min(workers, len(groups)))) as pool:
            list(pool.map(compile_group, groups))

    def serve(self, wave: list):
        """One wave through a fresh service: ``(answers, t_submit,
        t_return)``, an answer None where the request was not answered."""
        from repro.core.service import QueryService

        svc = QueryService(self.engine)
        t0 = time.perf_counter()
        reqs = [svc.submit(self.queries[i]) for i in wave]
        svc.flush()
        t1 = time.perf_counter()
        return [r.result if r.done else None for r in reqs], t0, t1


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool,
             control: bool = False, t_start: float | None = None,
             out=print) -> dict:
    """One run: the result object the CLI prints.  ``control`` puts the
    reference with bag semantics (joins keep duplicates) in the program's
    place for the window's answers, the control that has to come out not
    correct."""
    import jax

    t_start = time.perf_counter() if t_start is None else t_start
    rep: dict = {}
    cfg, mix = cell.config, cell.mix
    n, n_labels, edges = make_graph(cfg, seed)
    idx = build_index(n, n_labels, edges, int(cfg["k"]), rep)
    pool = make_pool(mix, n, n_labels, edges)
    waves = traffic.waves(mix, pool, np.random.default_rng(_seed(seed, 1)))
    sp = spans.Spans() if traced else None
    server = Server(idx, pool, waves, sp)
    usage = spans.HostUsage()
    wave_s: dict = {"warm": [], "window": []}

    def serve(wave, phase, span=_no_span):
        """One wave, with what the host did over it on standard error."""
        u0 = usage.snapshot()
        with span("wave"):
            answers, t0, t1 = server.serve(wave)
        d = usage.delta(u0, usage.snapshot())
        wave_s[phase].append(d["wall_s"])
        out(f"{phase} wave {len(wave_s[phase])}: " + " ".join(
            f"{k}={v:.3f}" if isinstance(v, float) else f"{k}={v}"
            for k, v in d.items()))
        return answers, t0, t1

    server.compile_ahead(int(mix.get("warm_threads", 8)))
    for wave in waves:
        serve(wave, "warm")
    rep["setup_s"] = time.perf_counter() - t_start
    out(f"setup: build_caps_s={rep['build_caps_s']:.3f} "
        f"build_s={rep['build_s']:.3f} setup_s={rep['setup_s']:.3f} "
        f"pairs={rep['n_pairs']} classes={rep['n_classes']} "
        f"index_bytes={rep['index_bytes']}")

    tel0 = dataclasses.replace(server.engine.telemetry)
    if sp is not None:
        sp.seconds.clear()
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if traced else None
    served: list = []  # (pool index, answer)
    latencies: list = []
    t_window = time.perf_counter()

    with spans.CompileClock() as clock:
        passes = 0
        while passes == 0 or time.perf_counter() - t_window < seconds:
            span = sp.span if traced and passes == 0 else _no_span
            if traced and passes == 0:  # the first pass, traced
                jax.profiler.start_trace(trace_dir)
            with span("window"):
                for wave in waves:
                    answers, t0, t1 = serve(wave, "window", span)
                    served.extend(zip(wave, answers))
                    latencies.extend([t1 - t0] * len(wave))
            if traced and passes == 0:
                jax.profiler.stop_trace()
            passes += 1
        window_s = time.perf_counter() - t_window
    usage.close()
    tel = server.engine.telemetry
    executed = tel.queries - tel0.queries
    dev = jax.devices()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in dev[:cell.chips])
    seq_pairs = index_pairs(idx)
    ctx = {"spans": dict(sp.seconds) if sp else {},
           "queries_executed": executed,
           "retry_rungs": tel.retry_rungs - tel0.retry_rungs,
           "compiles_in_window": clock.compiles, **rep}
    del server, idx

    t_ref = time.perf_counter()
    g = reference.Graph(n, n_labels, edges)
    expected = {}
    for i in sorted({i for i, _ in served}):
        expected[i] = reference.evaluate(g, pool[i][1])
    if control:
        bag = {i: reference.evaluate(g, pool[i][1], dedup=False)
               for i in expected}
        served = [(i, _rows(bag[i], n)) for i, _ in served]
    missing = sum(a is None for _, a in served)
    wrong = sum(a is not None and not reference.same_answer(a, expected[i], n)
                for i, a in served)
    bad_seqs = reference.index_mismatches(g, int(cfg["k"]), seq_pairs)
    ref_s = time.perf_counter() - t_ref

    checks = {"wrong_answers": [wrong, 0], "missing_answers": [missing, 0],
              "index_wrong_sequences": [len(bad_seqs), 0]}
    correct = all(v <= lim for v, lim in checks.values())
    metrics = {}
    result = {"correct": correct, "attempted": len(served),
              "failed": missing + wrong}
    if traced:
        red = trace.reduce(trace.load(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx.update(trace=red, traced_queries=len(pool),
                   traced_answer_pairs=sum(v.size for v in expected.values()),
                   peaks=_peaks(dev[0].device_kind))
        for m in cell.per_layer:
            reader = load_module(HERE / "metrics" / f"{m['name']}.py")
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device_extra = {"busy_s": red["busy_s"], "window_s": red["window_s"]}
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    else:
        values = {"qps": len(served) / window_s,
                  "p95_ms": 1000.0 * _p95(latencies),
                  "build_s": rep["build_s"], "setup_s": rep["setup_s"]}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
        device_extra = {}
    result["metrics"] = metrics
    result["device"] = {"platform": dev[0].platform,
                        "kind": dev[0].device_kind, "count": len(dev),
                        "memory_peak_bytes": peak, **device_extra}
    out(f"window: {len(served)} requests in {window_s:.3f} s over {passes} "
        f"passes of {len(waves)} waves, "
        f"{executed} executed, {ctx['retry_rungs']} retry rungs, "
        f"{clock.compiles} compiles; reference {ref_s:.3f} s")
    if bad_seqs:
        out(f"index: sequences that differ from the graph: {bad_seqs[:10]}")
    result["run"] = {
        "passes": passes, "window_s": window_s,
        "warm_waves_s": wave_s["warm"], "waves_s": wave_s["window"],
        "executed": executed, "retry_rungs": ctx["retry_rungs"],
        "compiles": clock.compiles, "reference_s": ref_s,
        "answer_pairs": sum(v.size for v in expected.values()),
        **{k: rep[k] for k in ("build_caps_s", "build_s", "index_bytes",
                               "n_pairs", "n_classes")}}
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result


def _no_span(_name):
    return contextlib.nullcontext()


def _rows(keys: np.ndarray, n: int) -> np.ndarray:
    return np.stack([keys // n, keys % n], 1).astype(np.int32)


def _p95(values: list) -> float:
    if len(values) < 2:
        return float(values[0])
    return statistics.quantiles(values, n=20)[18]


def _peaks(kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]
