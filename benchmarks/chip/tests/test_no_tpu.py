"""A run that finds no TPU, or no system under test, fails and prints no
result."""
import os
import shutil
import subprocess
import sys
import types

import pytest

import run as harness

CMD = [sys.executable, "benchmarks/chip/run.py", "--workload",
       "bsbm_dump.encoded", "--seed", "1", "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(CMD, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=300)


def test_a_run_on_the_cpu_fails_without_a_result():
    p = _run(harness.ROOT)
    assert p.returncode == 1
    assert p.stdout.strip() == ""
    assert "needs 1 TPU chip" in p.stderr


def test_a_run_with_only_the_benchmark_fails_without_a_result(tmp_path):
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns(".work", "__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_an_unknown_device_kind_is_an_error(monkeypatch):
    import jax
    fake = types.SimpleNamespace(platform="tpu", device_kind="TPU v99")
    monkeypatch.setattr(jax, "devices", lambda: [fake])
    _, cell, _, _ = harness.load_cell("bsbm_dump.encoded")
    with pytest.raises(RuntimeError, match="no entry for 'TPU v99'"):
        harness.find_chips(cell)


def test_too_few_chips_is_an_error(monkeypatch):
    import jax
    fake = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    monkeypatch.setattr(jax, "devices", lambda: [fake])
    _, cell, _, _ = harness.load_cell("bsbm_dump.encoded")
    assert harness.find_chips(dict(cell, chips=1))[1]["hbm_bytes"] > 0
    with pytest.raises(RuntimeError, match="needs 4 TPU"):
        harness.find_chips(dict(cell, chips=4))
