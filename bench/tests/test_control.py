"""The control, the reference one precision below the configuration's in
the program's place, comes out not correct against each cell's limits,
at a size a test run holds; the program itself comes out correct."""

import importlib
import os
import subprocess
import sys

import jax
import pytest

from conftest import BENCH, tiny_cell

ROOT = os.path.dirname(BENCH)


@pytest.mark.parametrize("cell", ["engine.wide", "replica.decode"])
def test_control_fails_a_limit(cell, interpret):
    c = tiny_cell(cell)
    mod = importlib.import_module(f"drivers.{c['config']['kind']}")
    d = mod.Driver(c["config"], c["traffic"], 2**31 + 3, c["limits"])
    d.setup()
    d.window(0.5)
    d.free()
    assert all(v["value"] <= v["limit"] for v in d.check().values())
    control = d.control()
    assert any(v > c["limits"][k] for k, v in control.items()), control


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "engine.wide",
         "--seed", "5", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_without_a_tpu_the_run_fails_and_prints_no_result():
    assert jax.devices()[0].platform != "tpu"
    r = _run(ROOT)
    assert r.returncode == 3
    assert r.stdout == ""
    assert "no TPU" in r.stderr


def test_the_benchmark_alone_cannot_run(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files
    has no program to measure."""
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path)
    assert r.returncode != 0
    assert r.stdout == ""
