#!/usr/bin/env python3
"""Bring-up smoke of the assessment path on a TPU: each phase drives a
user entry point at a size users run, and checks what comes out against
the repo's host references.  A smoke, not a benchmark: the seconds it
prints are one cold run each.

  python chip_smoke.py             # one chip: bulk, streamed, service
  python chip_smoke.py --chips 4   # four chips: placement, row-sharded
                                   # scan, mesh-batched rescans

Phases on one chip:

* bulk — ``qa.assess`` of ``synth_encoded(2**23)`` under ``jnp`` and
  ``fused_scan``: counts and both HLL register banks equal the numpy
  references (``counts_ref_np``, ``hll_fold_ref``) and each other, and the
  compiled ``fused_scan`` pass holds the TPU kernel (``tpu_custom_call``).
* streamed — a ~2^20-triple BSBM-style N-Triples file through
  ``streamed(262_144).pipelined()`` ``fused_scan``; equals a single-shot
  ``jnp`` run of the same file.
* service — an in-process ``QAServer`` (2 workers) takes two ~100k-triple
  uploads over HTTP, then a 1% append to one; every job is done, every
  report equals ``qa.assess`` of the same bytes, and the append rescans
  fewer segments than it reuses.

Phases on four chips (one process drives all four):

* placement — the sharded planes of 2^25 + 3 triples split evenly over
  the devices, none staged whole on device 0.
* row_sharded — ``fused_scan`` over ``make_assessment_mesh(4)`` on those
  triples (an uneven last shard) equals the references and a one-chip
  ``jnp`` run.
* segment_batch — ``eval_segment_batch`` of 8 store-sized segments equals
  per-segment ``eval_chunk`` on one device.

Every phase prints one JSON line: wall seconds, compile count and seconds
(persistent-cache hits among them), the devices' ``peak_bytes_in_use``,
and its checks.  The last line is ``{"ok": true, "device": {...}}`` only
when every phase passed on a TPU; otherwise the exit code is non-zero and
no such line is printed.  The compile cache is placed by
``repro.launch.enable_compile_cache``.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import shutil
import sys
import tempfile
import threading
import time
import traceback
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
BASE = ("http://bsbm.example.org/",)
TRIPLES_PER_PRODUCT = 4.63          # bsbm_ntriples' mean statement count
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileLog:
    """Counts XLA compilations (persistent-cache loads included) and their
    seconds, from JAX's monitoring events, in every thread."""

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


# -- references ----------------------------------------------------------------

def host_reference(planes, evaluator):
    """(counts, registers) of the numpy oracles, in the shapes
    ``AssessmentResult.counts``/``.registers`` take."""
    from repro.kernels.hll.ref import hll_fold_ref
    from repro.kernels.qap_count.ref import counts_ref_np
    from repro.rdf.triple_tensor import COL_S_FLAGS
    counts, regs = {}, {}
    valid = planes[:, COL_S_FLAGS] != 0
    for pln in evaluator.plans:
        vec = counts_ref_np(planes, pln.program, pln.n_counters)
        for m in pln.metrics:
            counts[m.name] = {c: int(vec[pln.slots[m.name][c]])
                              for c, _ in m.counters}
        for name, cols in pln.sketch_specs:
            regs[name] = hll_fold_ref(planes, cols, evaluator.hll_p,
                                      valid=valid)
    return counts, regs


def same_registers(a: dict, b: dict) -> bool:
    import numpy as np
    return a.keys() == b.keys() and all(np.array_equal(a[k], b[k])
                                        for k in a)


def same_result(a, b) -> bool:
    return (a.values == b.values and a.n_triples == b.n_triples
            and same_registers(a.registers, b.registers))


@functools.lru_cache(maxsize=1)
def synthetic(n_triples: int):
    """``synth_encoded(n_triples, seed=0)``, made once per process."""
    from repro.rdf import synth_encoded
    return synth_encoded(n_triples, seed=0)


def bsbm_bytes(n_triples: int, seed: int) -> bytes:
    from repro.rdf import bsbm_ntriples
    n_products = max(1, round(n_triples / TRIPLES_PER_PRODUCT))
    return bsbm_ntriples(n_products, seed=seed).encode()


# -- one chip ------------------------------------------------------------------

def phase_bulk(n_triples: int) -> dict:
    import jax
    import jax.numpy as jnp
    from repro import qa
    from repro.kernels import interpret_mode
    tt = synthetic(n_triples)
    out, t, res = {}, {}, {}
    for backend in ("jnp", "fused_scan"):
        t0 = time.perf_counter()
        res[backend] = qa.assess(tt, metrics="all", backend=backend)
        t[backend] = time.perf_counter() - t0
    t0 = time.perf_counter()
    ev = qa.pipeline().metrics("all").evaluator()
    ref_counts, ref_regs = host_reference(tt.planes, ev)
    t["host_reference"] = time.perf_counter() - t0
    fs = qa.pipeline().metrics("all").backend("fused_scan").evaluator()
    rows = -(-len(tt) // fs._row_multiple()) * fs._row_multiple()
    compiled = fs._pass_fns[0].lower(
        jax.ShapeDtypeStruct((rows, tt.planes.shape[1]), jnp.int32)
    ).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    for backend, r in res.items():
        out[f"{backend}_counts_eq_ref"] = r.counts == ref_counts
        out[f"{backend}_registers_eq_ref"] = same_registers(r.registers,
                                                            ref_regs)
    out["jnp_eq_fused_scan"] = same_result(res["jnp"], res["fused_scan"])
    # the TPU runs the compiled kernel; the CPU interprets it
    out["fused_scan_kernel_compiled"] = (
        ("tpu_custom_call" in text) == (not interpret_mode()))
    return {"checks": out, "seconds": t, "triples": len(tt),
            "sketches": sorted(ref_regs),
            "fused_scan_temp_bytes": getattr(mem, "temp_size_in_bytes", None)}


def phase_streamed(n_triples: int, chunk: int, work: str) -> dict:
    from repro import qa
    path = os.path.join(work, "bsbm.nt")
    with open(path, "wb") as f:
        f.write(bsbm_bytes(n_triples, seed=1))
    pipe = qa.pipeline().metrics("all").base(*BASE)
    t = {}
    t0 = time.perf_counter()
    streamed = (pipe.backend("fused_scan").streamed(chunk).pipelined()
                .run(path))
    t["streamed_fused_scan"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    single = pipe.run(path)
    t["single_shot_jnp"] = time.perf_counter() - t0
    stats = streamed.exec_stats
    return {"checks": {"streamed_eq_single_shot": same_result(streamed,
                                                               single),
                       "several_chunks": stats.chunks_total > 1},
            "seconds": t, "triples": single.n_triples,
            "chunks": stats.chunks_total, "mode": stats.mode}


def _http(method: str, url: str, body: bytes | None = None) -> dict:
    req = urllib.request.Request(url, data=body, method=method)
    with urllib.request.urlopen(req, timeout=120) as resp:
        return json.load(resp)


def _report_values(report: dict) -> dict:
    from repro.core.report import DQV
    return {m[DQV + "isMeasurementOf"]["@id"].rsplit(":", 1)[-1]:
            m[DQV + "value"] for m in report["measurements"]}


def phase_service(n_triples: int, work: str, segment_bytes: int = 0,
                  timeout: float = 900.0) -> dict:
    """``segment_bytes=0`` keeps the store's default segment size."""
    from repro import qa
    from repro.serve import QAServer, ServerConfig
    data = {"ds-a": bsbm_bytes(n_triples, seed=2),
            "ds-b": bsbm_bytes(n_triples, seed=3)}
    appended = bsbm_bytes(n_triples // 100, seed=4).replace(
        b"/Product", b"/AddedProduct")
    srv = QAServer(ServerConfig(store_root=os.path.join(work, "serve"),
                                metrics="all", backend="fused_scan",
                                base=BASE, workers=2, watch=False,
                                segment_bytes=segment_bytes),
                   port=0).start()
    api = f"http://{srv.host}:{srv.port}"
    deadline = time.time() + timeout

    def upload(name, body):
        return _http("PUT", f"{api}/datasets/{name}/data", body)["job"]["id"]

    def wait(jobs):
        done = {}
        while len(done) < len(jobs):
            for name, jid in jobs.items():
                j = _http("GET", f"{api}/datasets/{name}/jobs/{jid}")
                if j["state"] in ("done", "failed"):
                    done[name] = j
            if len(done) < len(jobs):
                if time.time() > deadline:
                    raise TimeoutError(f"jobs not finished: {jobs}")
                time.sleep(0.2)
        return done

    t = {}
    try:
        t0 = time.perf_counter()
        first = wait({n: upload(n, b) for n, b in data.items()})
        t["two_uploads"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        edit = wait({"ds-a": upload("ds-a", data["ds-a"] + appended)})["ds-a"]
        t["append_upload"] = time.perf_counter() - t0
        reports = {n: _http("GET", f"{api}/datasets/{n}/report")
                   for n in data}
    finally:
        srv.close()
    jobs = [*first.values(), edit]
    t0 = time.perf_counter()
    refs = {n: qa.assess(b, metrics="all", base=BASE) for n, b in
            [*data.items(), ("ds-a+", data["ds-a"] + appended)]}
    t["reference_assess"] = time.perf_counter() - t0
    want = {n: {k: float(v) for k, v in r.values.items()}
            for n, r in refs.items()}
    checks = {"all_jobs_done": all(j["state"] == "done" for j in jobs),
              "first_jobs_eq_assess": all(first[n].get("values") == want[n]
                                          for n in data)}
    for n, ref in (("ds-a", "ds-a+"), ("ds-b", "ds-b")):
        checks[f"{n}_report_eq_assess"] = (
            reports[n]["nTriples"] == refs[ref].n_triples
            and _report_values(reports[n]) == want[ref])
    es = edit.get("exec_stats") or {}
    checks["append_rescans_fewer_than_reused"] = (
        es.get("segments_rescanned", 1) < es.get("segments_reused", 0))
    return {"checks": checks, "seconds": t,
            "errors": [j["error"] for j in jobs if j.get("error")],
            "append_segments": {k: es.get(k) for k in
                                ("segments_rescanned", "segments_reused")}}


# -- four chips ----------------------------------------------------------------

def _mesh_pipeline(devices: int):
    from repro import qa
    from repro.launch.mesh import make_assessment_mesh
    return (qa.pipeline().metrics("all").backend("fused_scan")
            .shard(make_assessment_mesh(devices)))


def phase_placement(n_triples: int, devices: int) -> dict:
    import jax
    ev = _mesh_pipeline(devices).evaluator()
    devs = list(ev.mesh.devices.flat)

    def in_use():
        stats = [d.memory_stats() for d in devs]
        return [s["bytes_in_use"] if s else None for s in stats]

    before = in_use()
    arr = ev.device_planes(synthetic(n_triples))
    arr.block_until_ready()
    after = in_use()
    shards = sorted((s.device.id, s.data.shape[0])
                    for s in arr.addressable_shards)
    checks = {"one_shard_per_device": [d for d, _ in shards]
              == sorted(d.id for d in devs),
              "equal_shard_rows": len({r for _, r in shards}) == 1}
    delta = None
    if None not in before + after:          # the CPU reports no stats
        delta = [a - b for a, b in zip(after, before)]
        checks["even_bytes_per_device"] = (
            min(delta) > 0 and max(delta) <= 1.05 * min(delta))
    del arr
    return {"checks": checks, "shard_rows": [r for _, r in shards],
            "bytes_added_per_device": delta,
            "bytes_in_use_per_device": after,
            "platform": jax.default_backend()}


def phase_row_sharded(n_triples: int, devices: int) -> dict:
    from repro import qa
    tt = synthetic(n_triples)
    t = {}
    t0 = time.perf_counter()
    sharded = _mesh_pipeline(devices).run(tt)
    t["sharded_fused_scan"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    single = qa.assess(tt, metrics="all")
    t["one_chip_jnp"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    ref_counts, ref_regs = host_reference(
        tt.planes, qa.pipeline().metrics("all").evaluator())
    t["host_reference"] = time.perf_counter() - t0
    return {"checks": {
        "sharded_counts_eq_ref": sharded.counts == ref_counts,
        "sharded_registers_eq_ref": same_registers(sharded.registers,
                                                   ref_regs),
        "sharded_eq_one_chip_jnp": same_result(sharded, single),
        "uneven_last_shard": n_triples % devices != 0},
        "seconds": t, "triples": len(tt)}


def phase_segment_batch(segment_triples: int, devices: int,
                        n_segments: int = 8) -> dict:
    """Segments as the store hands them over: content-defined lengths,
    padded to one power-of-two bucket."""
    import numpy as np
    from repro import qa
    from repro.rdf import synth_encoded
    bucket = 1 << (segment_triples - 1).bit_length()
    tensors = [synth_encoded(segment_triples - 37 * i, seed=100 + i)
               .padded_to(bucket) for i in range(n_segments)]
    ev = _mesh_pipeline(devices).evaluator()
    one = qa.pipeline().metrics("all").backend("fused_scan").evaluator()
    t = {}
    t0 = time.perf_counter()
    batched = ev.eval_segment_batch(tensors)
    t["batched"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    alone = [one.eval_chunk(tt) for tt in tensors]
    t["per_segment_one_device"] = time.perf_counter() - t0
    equal = [all(np.array_equal(a, b) for a, b in zip(bc, ac))
             and same_registers(br, ar)
             for (bc, br), (ac, ar) in zip(batched, alone)]
    return {"checks": {"batch_eq_per_segment": all(equal),
                       "every_segment_returned": len(batched) == n_segments},
            "seconds": t, "bucket_rows": bucket}


# -- driver --------------------------------------------------------------------

def _peak_bytes() -> list:
    import jax
    stats = [d.memory_stats() for d in jax.devices()]
    return [s.get("peak_bytes_in_use") if s else None for s in stats]


def run_phase(name: str, log: CompileLog, fn, *args) -> dict:
    """Run one phase; its record, with ``ok`` false on any failed check or
    exception."""
    c0, s0, h0 = log.snapshot()
    t0 = time.perf_counter()
    try:
        rec = fn(*args)
        ok = all(rec["checks"].values())
    except Exception:  # noqa: BLE001 — a phase's failure is its result
        traceback.print_exc()
        rec, ok = {"error": traceback.format_exc(limit=3)}, False
    c1, s1, h1 = log.snapshot()
    rec = {"phase": name, "ok": ok,
           "wall_seconds": time.perf_counter() - t0,
           "compiles": c1 - c0, "compile_seconds": s1 - s0,
           "compile_cache_hits": h1 - h0,
           "peak_bytes_in_use": _peak_bytes(), **rec}
    print(json.dumps(rec, default=str), flush=True)
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip phases")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"chip_smoke: no repro package under {ROOT}/src",
              file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    from repro.launch import enable_compile_cache

    devices = jax.devices()
    found = (f"{devices[0].platform} ({devices[0].device_kind}) x "
             f"{len(devices)}")
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {found}", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX found {found}", file=sys.stderr)
        return 1
    print(f"# chip_smoke on {found}; compile cache "
          f"{enable_compile_cache()}", flush=True)

    log = CompileLog()
    work = tempfile.mkdtemp(prefix=".chip_smoke-", dir=ROOT)
    try:
        if args.chips == 4:
            n = (1 << 25) + 3
            recs = [run_phase("placement", log, phase_placement, n, 4),
                    run_phase("row_sharded", log, phase_row_sharded, n, 4),
                    run_phase("segment_batch", log, phase_segment_batch,
                              12_000, 4)]
        else:
            recs = [run_phase("bulk", log, phase_bulk, 1 << 23),
                    run_phase("streamed", log, phase_streamed, 1 << 20,
                              262_144, work),
                    run_phase("service", log, phase_service, 100_000, work)]
    finally:
        log.close()
        shutil.rmtree(work, ignore_errors=True)
    failed = [r["phase"] for r in recs if not r["ok"]]
    if failed:
        print(f"chip_smoke: FAILED phases {failed}", flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
