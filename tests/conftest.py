"""Shared fixtures. NOTE: no XLA_FLAGS here — tests see the real device
count (1 CPU); multi-device tests spawn subprocesses with their own flags."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


def run_subprocess_devices(n_devices: int, code: str) -> dict:
    """Run `code` with n fake XLA devices; it must print one JSON line.
    The child is pinned to the CPU: these are CPU rehearsals, and on a
    machine with an accelerator the child must not try to take it."""
    env = {**os.environ,
           "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": f"--xla_force_host_platform_device_count={n_devices}",
           "PYTHONPATH": "src"}
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, check=False)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])
