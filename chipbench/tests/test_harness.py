"""The harness on the CPU at a tiny size: cells found by name, every
answer checked against the reference, the control and the faults that
``correct`` has to catch, and the CLI's refusal off the chip."""

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from chipbench import harness, reference, traffic
from chipbench.tools import readings

ROOT = Path(harness.__file__).resolve().parents[1]
SEED = 2**31 + 977  # larger than 32 signed bits hold, as run seeds may be


def tiny(name="gmark-join", n=200, graph_seed=None, query_seed=None):
    cell = harness.find_cell(name)
    settings = [f"config.n_vertices={n}", "mix.pool=16", "mix.wave=8",
                "mix.warm_threads=2"]
    if graph_seed is not None:
        settings.append(f"config.graph_seed={graph_seed}")
    if query_seed is not None:
        settings.append(f"mix.query_seed={query_seed}")
    readings.override(cell, settings)
    return cell


def run(cell, **kw):
    lines = []
    res = harness.run_cell(cell, SEED, 0.2, False, out=lines.append, **kw)
    rungs = int(re.search(r"(\d+) retry rungs", "\n".join(lines)).group(1))
    return res, rungs


@pytest.mark.parametrize("name", [w["name"] for w in
                                  harness.load_benchmark()["workloads"]])
def test_cell_found_by_name_from_its_files(name):
    bench = harness.load_benchmark()
    cell = harness.find_cell(name, bench)
    entry = {w["name"]: w for w in bench["workloads"]}[name]
    assert cell.chips == entry["chips"]
    assert (harness.HERE / "generators"
            / f"{cell.config['generator']}.py").is_file()
    assert set(cell.mix["templates"]) <= set(traffic.TEMPLATES)
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    for m in cell.per_layer:
        reader = harness.load_module(harness.HERE / "metrics"
                                     / f"{m['name']}.py")
        assert callable(reader.read)
    cfg_entry = {c["name"]: c for c in bench["configs"]}[entry["config"]]
    for key in cfg_entry["reduced"]:
        assert key in cell.config


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        harness.find_cell("no-such-cell")


def test_seed_permutes_ids_and_order_but_not_the_work():
    cell = tiny()
    a = harness.make_graph(cell.config, 1)
    b = harness.make_graph(cell.config, 2)
    assert not np.array_equal(a[2], b[2])
    for x in (a, b):
        deg = np.bincount(x[2][:, 0], minlength=x[0])
        assert sorted(deg) == sorted(np.bincount(a[2][:, 0], minlength=a[0]))
    pool = harness.make_pool(cell.mix, *a)
    assert len({q for _, q in pool}) == len(pool)
    assert pool == harness.make_pool(cell.mix, *b)
    g = reference.Graph(*a)
    assert all(reference.evaluate(g, q).size for _, q in pool)
    w1 = traffic.waves(cell.mix, pool, np.random.default_rng(1))
    w2 = traffic.waves(cell.mix, pool, np.random.default_rng(2))
    assert sorted(map(sorted, w1)) == sorted(map(sorted, w2))


@pytest.mark.parametrize("name", ["gmark-join"])
def test_every_answer_of_every_wave_matches_the_reference(name):
    res, _ = run(tiny(name))
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 8 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert all(c["value"] == 0 for c in res["checks"].values())
    assert set(res["metrics"]) == {"qps", "p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    run_ = res["run"]
    assert len(run_["warm_waves_s"]) == 2
    assert len(run_["waves_s"]) == 2 * run_["passes"]
    assert sum(run_["waves_s"]) <= run_["window_s"]


# The benchmark's runs serve one graph and one pool (a run's seed only
# permutes ids and order, so that every seed measures the same work):
# these read the program on other graphs and pools of the cell's schema.
@pytest.mark.parametrize("graph_seed,query_seed", [(1, 1), (2, 7), (3, 12)])
def test_every_answer_matches_the_reference_on_other_graphs_and_pools(
        graph_seed, query_seed):
    res, _ = run(tiny(graph_seed=graph_seed, query_seed=query_seed))
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 16 and res["failed"] == 0


def test_settings_override_a_cells_config_and_mix():
    cell = harness.find_cell("gmark-join")
    readings.override(cell, ["config.n_vertices=300", "mix.query_seed=4"])
    assert cell.config["n_vertices"] == 300
    assert cell.mix["query_seed"] == 4
    assert cell.config["k"] == harness.find_cell("gmark-join").config["k"]
    with pytest.raises(ValueError):
        readings.override(cell, ["n_vertices=300"])


def test_lanes_that_climbed_retry_rungs_are_checked(monkeypatch):
    from repro.core.engine import Engine, QueryCaps

    monkeypatch.setattr(Engine, "estimate_caps",
                        lambda self, *a, **k: QueryCaps(16, 64, 128))
    res, rungs = run(tiny())
    assert rungs > 0
    assert res["correct"], res["checks"]


def test_control_with_bag_semantics_is_not_correct():
    res, _ = run(tiny(), control=True)
    assert not res["correct"]
    assert res["checks"]["wrong_answers"]["value"] > 0


def test_an_answer_altered_where_it_is_produced_is_caught(monkeypatch):
    from repro.core.backend import LocalBackend

    real = LocalBackend.harvest_batch

    def altered(self, handle):
        rows, overflow = real(self, handle)
        return [r if r is None or len(r) == 0 else r[1:] for r in rows], \
            overflow

    monkeypatch.setattr(LocalBackend, "harvest_batch", altered)
    res, _ = run(tiny())
    assert not res["correct"]
    assert res["checks"]["wrong_answers"]["value"] > 0


def test_half_of_the_batch_left_out_is_caught(monkeypatch):
    from repro.core.service import QueryService

    real = QueryService._take_round

    def half(self):
        return real(self)[::2]

    monkeypatch.setattr(QueryService, "_take_round", half)
    res, _ = run(tiny())
    assert not res["correct"]
    assert res["checks"]["missing_answers"]["value"] > 0


def test_an_index_entry_altered_where_it_is_built_is_caught(monkeypatch):
    from repro.core import index as cindex

    real = cindex.build_index_arrays

    class Altered:
        def lower(self, *args):
            exe = real.lower(*args).compile()

            class Compiled:
                def compile(self):
                    def call(dg):
                        a = exe(dg)
                        return a._replace(c2p_u=a.c2p_u.at[0].add(1))
                    return call
            return Compiled()

    monkeypatch.setattr(cindex, "build_index_arrays", Altered())
    res, _ = run(tiny())
    assert not res["correct"]
    assert res["checks"]["index_wrong_sequences"]["value"] > 0


def test_reference_follows_the_semantics():
    g = reference.Graph(4, 1, np.array([[0, 1, 0], [1, 2, 0], [2, 0, 0]]))
    keys = reference.evaluate(g, traffic.TEMPLATES["Ti"](0, 0, 0))
    assert sorted(keys.tolist()) == [0, 5, 10]  # the 3-cycle's vertices
    c2 = reference.evaluate(g, traffic.TEMPLATES["C2"](0, 1))
    assert sorted(c2.tolist()) == [0, 5, 10]  # forward then back
    assert reference.same_answer(np.array([[0, 0], [1, 1], [2, 2]]), keys, 4)
    assert not reference.same_answer(np.array([[0, 0], [0, 0], [1, 1],
                                               [2, 2]]), keys, 4)


def _cli(cwd, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "gmark-join",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def _has_result(stdout):
    for line in stdout.splitlines():
        try:
            if "correct" in json.loads(line):
                return True
        except (ValueError, TypeError):
            continue
    return False


def test_cli_exits_nonzero_with_no_result_off_the_chip(tmp_path):
    p = _cli(ROOT, tmp_path)
    assert p.returncode != 0
    assert not _has_result(p.stdout)
    assert "TPU" in p.stderr


def test_cli_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    alone = tmp_path / "alone"
    shutil.copytree(ROOT / "chipbench", alone / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", alone)
    p = _cli(alone, tmp_path)
    assert p.returncode != 0
    assert not _has_result(p.stdout)
