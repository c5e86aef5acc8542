"""Benchmark utilities: timing, dataset building, worker simulation."""
from __future__ import annotations

import json
import os
import statistics
import subprocess
import sys
import time

import numpy as np

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "results")


def timeit(fn, repeats: int = 3, warmup: int = 1):
    for _ in range(warmup):
        out = fn()
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn()
        times.append(time.perf_counter() - t0)
    return out, statistics.mean(times), (statistics.stdev(times)
                                         if len(times) > 1 else 0.0)


def save_json(name: str, payload):
    os.makedirs(RESULTS_DIR, exist_ok=True)
    path = os.path.join(RESULTS_DIR, name)
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
    return path


def run_with_devices(n_devices: int, code: str) -> dict:
    """Run a python snippet in a subprocess with n fake XLA CPU devices
    (pinned to the CPU, so it never contends for an accelerator); the
    snippet must print one JSON line to stdout."""
    env = {**os.environ,
           "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": f"--xla_force_host_platform_device_count={n_devices}",
           "PYTHONPATH": "src"}
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, check=False)
    if proc.returncode != 0:
        raise RuntimeError(proc.stderr[-2000:])
    return json.loads(proc.stdout.strip().splitlines()[-1])


def makespan(chunk_times: list[float], n_workers: int) -> float:
    """Greedy longest-processing-time makespan: the wall-clock a w-worker
    cluster would need for these measured chunk latencies.

    This container has ONE core, so multi-worker wall-clock cannot be
    measured directly; per-chunk compute times are REAL measurements and the
    schedule is the same greedy assignment the chunk scheduler uses.
    Documented as a simulation in EXPERIMENTS.md.
    """
    loads = [0.0] * n_workers
    for t in sorted(chunk_times, reverse=True):
        i = int(np.argmin(loads))
        loads[i] += t
    return max(loads)
