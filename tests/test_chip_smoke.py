"""chip_smoke.py's phases in-process on the CPU at a tiny size: every
answer equals the oracle, and off the chip the script prints no result
and exits non-zero."""

import importlib.util
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_one_chip_phases_match_the_oracle(smoke):
    lines = []
    rep = smoke.serve_one_chip(240, seed=0, n_per=1, out=lines.append)
    assert rep["served"] == 12  # one query per Fig. 5 template
    assert rep["matched"] == rep["matched_cold"] == rep["served"]
    assert rep["serve_compiles"] == 0  # compiles stay in the first pass
    assert rep["impl"] == {"conj_classes": "xla", "materialize": "xla"}
    assert rep["ok"]
    text = "\n".join(lines)
    for key in ("vertices=240", "classes=", "device_bytes=",
                "device_build_s=", "compile_s=", "serve_s=",
                "matched 12/12", "peak_bytes_in_use=",
                "conj_classes=xla materialize=xla"):
        assert key in text, key


def test_four_chip_phase_on_four_host_devices():
    """The ``--chips 4`` phase on four virtual CPU devices (its own
    process: this one keeps one device): every index leaf placed, every
    answer bit-identical to one device and equal to the oracle."""
    code = textwrap.dedent(f"""
        import importlib.util, json
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", {str(ROOT / "chip_smoke.py")!r})
        smoke = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(smoke)
        rep = smoke.serve_four_chips(240, seed=0, out=print)
        print(json.dumps({{k: rep[k] for k in (
            "ok", "shards_placed", "served", "identical", "matched")}}))
    """)
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    run = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, cwd=ROOT, timeout=600)
    assert run.returncode == 0, run.stderr[-4000:]
    lines = run.stdout.splitlines()
    rep = json.loads(lines[-1])
    assert rep == {"ok": True, "shards_placed": True, "served": 12,
                   "identical": 12, "matched": 12}, run.stdout
    assert any(ln.startswith("sharding: 17/17") for ln in lines)


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_off_the_chip_no_result_and_nonzero_exit(smoke, capsys, argv):
    assert jax.devices()[0].platform != "tpu"
    rc = smoke.main(argv + ["--seed", "3"])
    out = capsys.readouterr().out
    assert rc != 0
    assert '"ok"' not in out
    for line in out.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


def test_script_alone_exits_nonzero(smoke, tmp_path, monkeypatch, capsys):
    """Copied into a directory without the repository, the script finds
    no ``src/repro`` and stops before importing anything of it."""
    monkeypatch.setattr(smoke, "ROOT", tmp_path)
    rc = smoke.main([])
    assert rc != 0
    assert '"ok"' not in capsys.readouterr().out
