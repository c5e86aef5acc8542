#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark once, and print its result as the
last line of standard output.

    python3 benchmarks/chip/run.py --workload bsbm_dump.ntriples \\
        --seed 7 --seconds 45 --trace 0

Everything is found by name.  The cell is an entry of ``workloads`` in
``BENCHMARK.json`` at the root of the checkout; it names a configuration,
``configs/<config>.json``, and a traffic mix, ``traffic/<traffic>.json``,
which names the runner that runs it, ``runners/<runner>.py``.  Each
metric, end to end or per layer, is read by ``metrics/<metric>.py``.

A run: checks that JAX sees a TPU with as many chips as the cell asks for
and that ``peaks.json`` knows it (otherwise it exits 1 and prints no
result); sets up (JAX start, data from ``--seed``, the store or the files
the traffic needs, and one pass over every shape the window will use),
which is ``setup_s``; runs the traffic for ``--seconds``, closing the
window at the end of the first unit of work that finishes after it; reads
the device's peak memory; then compares every answer of the window with
the plain reference (``reference/``) and prints each gap beside its limit.
``--trace 1`` runs the window under the profiler and prints the per-layer
metrics instead of the end-to-end ones.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()        # set-up is counted from process start

import argparse                 # noqa: E402
import gc                       # noqa: E402
import importlib.util           # noqa: E402
import json                     # noqa: E402
import os                       # noqa: E402
import shutil                   # noqa: E402
import sys                      # noqa: E402
import threading                # noqa: E402
import traceback                # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
WORK = os.path.join(HERE, ".work")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
MAX_FAILED_STEPS = 3


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``<kind>/<name>.py`` under the benchmark, as a module."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"{kind}.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class CompileLog:
    """Counts XLA compilations (persistent-cache loads among them) and
    their seconds, from JAX's monitoring events, in every thread."""

    def __init__(self):
        import jax.monitoring
        self._lock = threading.Lock()
        self.compiles, self.seconds, self.cache_hits = 0, 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **_):
        if event == COMPILE_EVENT:
            with self._lock:
                self.compiles += 1
                self.seconds += duration_secs

    def _event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            with self._lock:
                self.cache_hits += 1

    def snapshot(self) -> tuple:
        with self._lock:
            return self.compiles, self.seconds, self.cache_hits

    def close(self) -> None:
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)


class GcLog:
    """Pauses of Python's garbage collector, from ``gc.callbacks``: a
    look for the host stalls that some windows show."""

    def __init__(self):
        self.pauses: list[tuple[int, float]] = []   # (generation, s)
        self._t0 = 0.0
        gc.callbacks.append(self._event)

    def _event(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t0))

    def summary(self) -> str:
        total = sum(s for _, s in self.pauses)
        gen, longest = max(self.pauses, key=lambda p: p[1],
                           default=(0, 0.0))
        return (f"{len(self.pauses)} collections, {total:.4f} s, longest "
                f"{longest:.4f} s (generation {gen})")

    def close(self) -> None:
        gc.callbacks.remove(self._event)


class Run:
    """What the metric readers see of one run."""

    def __init__(self, peaks: dict):
        self.peaks = peaks              # the chip's entry in peaks.json
        self.setup_s = 0.0
        self.window_s = 0.0
        self.steps: list[dict] = []     # one record per unit of work
        self.extra: dict = {}           # what the runner adds after it
        self.trace = None               # trace_reduce.Trace, traced runs


def cell_metrics(bench: dict, cell: str, trace: bool) -> list[dict]:
    """The metrics a cell reports: end to end with ``--trace 0``, per
    layer with ``--trace 1``."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


def device_record(devices) -> dict:
    stats = [d.memory_stats() or {} for d in devices]   # None on the CPU
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices),
            "memory_peak_bytes": max(int(s.get("peak_bytes_in_use", 0))
                                     for s in stats)}


def load_cell(name: str):
    """(BENCHMARK.json, the cell, its configuration, its traffic mix)."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise LookupError(f"no workload {name!r} in BENCHMARK.json; "
                          f"there are {sorted(cells)}")
    cell = cells[name]
    return (bench, cell, load_json(HERE, "configs", cell["config"] + ".json"),
            load_json(HERE, "traffic", cell["traffic"] + ".json"))


def find_chips(cell: dict):
    """(the cell's devices, their peaks), or an error naming what JAX
    found: a run needs TPU chips that ``peaks.json`` knows."""
    import jax
    devices = jax.devices()
    found = (f"{devices[0].platform} ({devices[0].device_kind}) x "
             f"{len(devices)}")
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        raise RuntimeError(f"{cell['name']} needs {cell['chips']} TPU "
                           f"chip(s); JAX found {found}")
    peaks = load_json(HERE, "peaks.json")["devices"].get(
        devices[0].device_kind)
    if peaks is None:
        raise RuntimeError(f"peaks.json has no entry for "
                           f"{devices[0].device_kind!r}")
    return devices[:cell["chips"]], peaks


def execute(bench, cell, config, traffic, seed: int, seconds: float,
            trace: bool, devices, peaks) -> dict:
    """Set up, run the window, compare with the reference: the result."""
    import jax
    from repro.launch import enable_compile_cache

    import trace_reduce
    from reference import compare

    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    clog = CompileLog()
    name = cell["name"]
    work = os.path.join(WORK, name)
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    run = Run(peaks)
    runner = load_module("runners", traffic["runner"]).Runner(
        config, traffic, seed, work)
    failed = 0
    try:
        runner.setup()
        # the set-up's objects (the dump's lines among them) stay out of
        # the collections that the window's allocations set off
        gc.collect()
        gc.freeze()
        run.setup_s = time.perf_counter() - T0
        c_setup = clog.snapshot()
        log(f"# {name} seed {seed} on {devices[0].device_kind} x "
            f"{len(devices)}; compile cache {cache_dir}; set-up "
            f"{run.setup_s:.3f} s, {c_setup[0]} compiles "
            f"({c_setup[1]:.3f} s, {c_setup[2]} cache hits); data "
            f"{json.dumps(runner.describe())}")
        trace_dir = os.path.join(work, "trace")
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        gclog = GcLog()
        t_start = time.perf_counter()
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            while True:
                try:
                    run.steps.append(runner.step())
                except Exception:  # noqa: BLE001 — a failed step is counted
                    log(traceback.format_exc())
                    failed += 1
                    if failed >= MAX_FAILED_STEPS:
                        break
                if time.perf_counter() - t_start >= seconds:
                    break
        run.window_s = time.perf_counter() - t_start
        gclog.close()
        if trace:
            jax.profiler.stop_trace()
        c_window = clog.snapshot()
        device = device_record(devices)
        log(f"# window {run.window_s:.3f} s, {len(run.steps)} steps, "
            f"{failed} failed, {c_window[0] - c_setup[0]} compiles in the "
            f"window ({c_window[2] - c_setup[2]} cache hits); garbage "
            f"collector in the window: {gclog.summary()}")
        if trace:
            run.trace = trace_reduce.load(trace_dir)
            shutil.rmtree(trace_dir, ignore_errors=True)
            device["busy_s"] = run.trace.busy_s()
            device["window_s"] = run.trace.window_s()
            run.extra = runner.traced_extra()
        for line in runner.notes(run.steps):
            log(line)
        gaps = compare.worst(runner.gaps(run.steps))
    finally:
        gc.unfreeze()
        runner.close()
        clog.close()
        shutil.rmtree(work, ignore_errors=True)

    metrics = {}
    for m in cell_metrics(bench, name, trace):
        if m["name"] == "setup_s":
            value = run.setup_s
        else:
            value = load_module("metrics", m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    correct = failed == 0 and bool(run.steps) and compare.within(gaps)
    checks = {k: {"value": gaps[k], "limit": compare.LIMITS[k]}
              for k in compare.LIMITS}
    for k, c in checks.items():
        log(f"check {k} {c['value']!r} limit {c['limit']!r}")
    log(f"correct {correct}")
    out = {"correct": correct, "attempted": len(run.steps) + failed,
           "failed": failed, "metrics": metrics, "device": device}
    if run.trace is not None:
        out["breakdown"] = run.trace.breakdown()
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        bench, cell, config, traffic = load_cell(args.workload)
    except (LookupError, OSError) as e:
        log(f"run: {e}")
        return 2
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        log(f"run: the system under test is missing: no {src}/repro")
        return 2
    sys.path.insert(0, src)
    sys.path.insert(0, HERE)
    try:
        devices, peaks = find_chips(cell)
    except RuntimeError as e:
        log(f"run: {e}")
        return 1
    out = execute(bench, cell, config, traffic, args.seed, args.seconds,
                  bool(args.trace), devices, peaks)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
