"""The ``repro.serve`` daemon: end-to-end HTTP service over the segment
store (register → upload → job → DQV report/history), incremental reuse
across uploads, per-dataset job serialization with cross-dataset
concurrency, alert rules + webhooks, racing an external CLI ``--store``
run on the same store dir, and the registry's name validation."""
import io
import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from repro import qa
from repro.rdf import bsbm_ntriples
from repro.serve import (QAServer, RegistryError, ServerConfig, parse_rule,
                         validate_name)

BASE = ("http://bsbm.example.org/",)
SEG = 4096


@pytest.fixture()
def server(tmp_path):
    srv = QAServer(ServerConfig(
        store_root=os.fspath(tmp_path / "root"), metrics="paper",
        base=BASE, workers=2, segment_bytes=SEG, poll_interval=0.1),
        port=0).start()
    yield srv
    srv.close()


def req(srv, method, path, body=None, headers=None):
    """(status, parsed-or-raw body); 4xx/5xx don't raise."""
    r = urllib.request.Request(
        f"http://127.0.0.1:{srv.port}{path}", data=body, method=method,
        headers=headers or {})
    try:
        with urllib.request.urlopen(r, timeout=30) as resp:
            raw = resp.read()
            status = resp.status
            ctype = resp.headers.get("Content-Type", "")
    except urllib.error.HTTPError as e:
        raw = e.read()
        status = e.code
        ctype = e.headers.get("Content-Type", "")
    if ctype.startswith("application/json"):
        return status, json.loads(raw)
    return status, raw


def wait_job(srv, name, job_id, timeout=120.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        st, job = req(srv, "GET", f"/datasets/{name}/jobs/{job_id}")
        assert st == 200, job
        if job["state"] in ("done", "failed"):
            return job
        time.sleep(0.05)
    raise AssertionError(f"job {job_id} still {job['state']} after "
                         f"{timeout}s")


def upload(srv, name, text):
    st, doc = req(srv, "PUT", f"/datasets/{name}/data",
                  body=text.encode())
    assert st == 202, doc
    return doc["job"]["id"]


# -- end-to-end ----------------------------------------------------------------

def test_upload_to_report_history_bit_identical_to_cold(server):
    data = bsbm_ntriples(100, seed=0)
    job = wait_job(server, "ds1", upload(server, "ds1", data))
    assert job["state"] == "done", job["error"]

    cold = qa.assess(data, metrics="paper", base=BASE)
    assert job["values"] == {k: float(v) for k, v in
                             sorted(cold.values.items())}
    assert job["n_triples"] == cold.n_triples

    # DQV JSON report over HTTP: same values, service provenance included
    st, rep = req(server, "GET", "/datasets/ds1/report")
    assert st == 200
    assert rep["@id"] == "urn:repro:dataset:ds1"
    assert rep["nTriples"] == cold.n_triples
    served = {m["http://www.w3.org/ns/dqv#isMeasurementOf"]["@id"]
              .rsplit(":", 1)[1]: m["http://www.w3.org/ns/dqv#value"]
              for m in rep["measurements"]}
    assert served == dict(cold.values)
    es = rep["execStats"]
    assert es["bytes_rescanned"] == es["bytes_total"] > 0  # cold first run
    assert es["segments_reused"] == 0

    # N-Triples serialization via ?format= and via Accept:
    st, nt = req(server, "GET", "/datasets/ds1/report?format=nt")
    assert st == 200 and isinstance(nt, bytes)
    from repro.rdf.parser import parse_ntriples
    assert len(parse_ntriples(nt.decode())) == 6 * len(cold.values)
    st2, nt2 = req(server, "GET", "/datasets/ds1/report",
                   headers={"Accept": "application/n-triples"})
    assert st2 == 200 and nt2 == nt

    # history trend
    st, hist = req(server, "GET", "/datasets/ds1/history")
    assert st == 200 and hist["snapshots"] == 1
    assert hist["metrics"]["L1"]["latest"] == cold.values["L1"]

    # registers: a direct incremental run over the daemon's store reuses
    # every daemon-frozen segment and reproduces the cold registers
    # bit-for-bit
    warm = qa.assess(data, metrics="paper", base=BASE,
                     store=server.registry.store_dir("ds1"),
                     segment_bytes=SEG)
    assert warm.exec_stats.segments_rescanned == 0
    assert warm.values == cold.values
    assert set(warm.registers) == set(cold.registers)
    for k in cold.registers:
        assert np.array_equal(warm.registers[k], cold.registers[k])

    # liveness + observability responded throughout
    st, hz = req(server, "GET", "/healthz")
    assert st == 200 and hz["status"] == "ok" and hz["datasets"] == 1
    st, prom = req(server, "GET", "/metrics")
    text = prom.decode()
    assert 'repro_assessments_total{dataset="ds1",state="done"} 1' in text
    assert "repro_http_requests_total" in text
    assert "repro_job_queue_depth" in text
    assert "repro_bytes_rescanned_total" in text


def test_metrics_export_span_seconds_and_queue_wait(server):
    """A finished job adds its per-stage host seconds (self time of each
    program span), the program's counters and its wait in the queue to
    /metrics."""
    # one 300 B literal, deduplicated in a wide tier
    data = bsbm_ntriples(200, seed=5) + (
        f'<http://s> <http://p> "{"w" * 298}" .\n')
    job = wait_job(server, "obs", upload(server, "obs", data))
    assert job["state"] == "done", job
    st, prom = req(server, "GET", "/metrics")
    assert st == 200
    samples = {}
    for line in prom.decode().splitlines():
        if not line.startswith("#"):
            key, _, value = line.rpartition(" ")
            samples[key] = float(value)
    spans = {k[len('repro_span_seconds_total{span="'):-2]: v
             for k, v in samples.items()
             if k.startswith("repro_span_seconds_total{")}
    assert {"qa.run", "store.segment", "store.freeze", "ingest.tokenize",
            "scan.wait", "scan.finalize"} <= set(spans)
    assert all(v >= 0 for v in spans.values())
    count = 'repro_program_count_total{counter="%s"}'
    assert samples[count % "ingest.lines"] == len(data.splitlines())
    assert samples[count % "ingest.bytes"] == len(data.encode())
    assert 0 <= samples[count % "ingest.fallback_lines"] <= len(
        data.splitlines())
    assert samples[count % "ingest.wide_tokens"] == 1
    assert samples[count % "transfer.bytes"] > 0
    assert samples["repro_job_queue_wait_seconds_count"] == 1
    assert samples["repro_job_queue_wait_seconds_sum"] >= 0


def test_second_upload_rescans_only_changed_segments(server):
    data = bsbm_ntriples(100, seed=3)
    job1 = wait_job(server, "inc", upload(server, "inc", data))
    assert job1["state"] == "done", job1["error"]
    assert job1["exec_stats"]["segments_reused"] == 0

    edited = data + bsbm_ntriples(6, seed=77)
    job2 = wait_job(server, "inc", upload(server, "inc", edited))
    assert job2["state"] == "done", job2["error"]
    es = job2["exec_stats"]
    assert es["segments_reused"] >= 1          # append is edit-local
    assert 0 < es["bytes_rescanned"] < es["bytes_total"]

    cold = qa.assess(edited, metrics="paper", base=BASE)
    assert job2["values"] == {k: float(v) for k, v in
                              sorted(cold.values.items())}
    st, hist = req(server, "GET", "/datasets/inc/history")
    assert hist["snapshots"] == 2


# -- concurrency ---------------------------------------------------------------

def test_two_datasets_in_parallel_one_dataset_serialized(server):
    blocks = [bsbm_ntriples(60, seed=s) for s in (1, 2, 3)]
    other = bsbm_ntriples(80, seed=9)
    # burst: three uploads to ds_a (must serialize), one to ds_b
    # (free to run on the second worker while ds_a works its queue)
    ids_a = [upload(server, "ds_a", b) for b in blocks]
    id_b = upload(server, "ds_b", other)
    jobs_a = [wait_job(server, "ds_a", i) for i in ids_a]
    job_b = wait_job(server, "ds_b", id_b)
    assert all(j["state"] == "done" for j in jobs_a + [job_b]), \
        [j["error"] for j in jobs_a + [job_b]]
    # per-dataset serialization: no two ds_a jobs overlapped, FIFO order
    for prev, nxt in zip(jobs_a, jobs_a[1:]):
        assert nxt["started_at"] >= prev["finished_at"]
    # each dataset's final report reflects its last upload, exactly
    for name, text in (("ds_a", blocks[-1]), ("ds_b", other)):
        cold = qa.assess(text, metrics="paper", base=BASE)
        _, rep = req(server, "GET", f"/datasets/{name}/report")
        vals = {m["http://www.w3.org/ns/dqv#isMeasurementOf"]["@id"]
                .rsplit(":", 1)[1]: m["http://www.w3.org/ns/dqv#value"]
                for m in rep["measurements"]}
        assert vals == dict(cold.values)
    # ds_a history holds all three snapshots in upload order
    _, hist = req(server, "GET", "/datasets/ds_a/history")
    assert hist["snapshots"] == 3
    assert hist["metrics"]["L1"]["latest"] == \
        qa.assess(blocks[-1], metrics="paper", base=BASE).values["L1"]


def test_daemon_job_races_external_cli_store_run(server, tmp_path):
    """A daemon job and an external ``repro.launch.assess --store`` run
    hammer the SAME store dir concurrently — the PR 5 flock/CAS path,
    exercised end-to-end through HTTP.  Both must succeed and leave a
    consistent store."""
    data = bsbm_ntriples(120, seed=5)
    nt_path = tmp_path / "race.nt"
    nt_path.write_text(data)
    first = wait_job(server, "race", upload(server, "race", data))
    assert first["state"] == "done", first["error"]
    store_dir = server.registry.store_dir("race")

    env = {**os.environ, "PYTHONPATH": "src"}
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.launch.assess",
         "--nt", os.fspath(nt_path), "--store", store_dir,
         "--segment-bytes", str(SEG), "--metrics", "paper",
         "--base", BASE[0]],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True)
    # keep daemon jobs landing on the same store while the CLI runs
    raced = 0
    while proc.poll() is None:
        st, doc = req(server, "POST", "/datasets/race/assess")
        assert st == 202, doc
        job = wait_job(server, "race", doc["job"]["id"])
        assert job["state"] == "done", job["error"]
        raced += 1
    out, err = proc.communicate(timeout=60)
    assert proc.returncode == 0, err[-3000:]
    assert raced >= 1
    # CLI saw the same values the daemon serves
    cold = qa.assess(data, metrics="paper", base=BASE)
    cli_values = dict(
        line.split() for line in out.strip().splitlines())
    assert {k: float(v) for k, v in cli_values.items()} == \
        {k: float(f"{v:.6f}") for k, v in cold.values.items()}
    # the store survived the race: a fresh run is pure reuse
    after = qa.assess(data, metrics="paper", base=BASE,
                      store=store_dir, segment_bytes=SEG)
    assert after.exec_stats.segments_rescanned == 0
    assert after.values == cold.values


# -- source registration + watcher ---------------------------------------------

def test_registered_source_path_is_watched(server, tmp_path):
    src = tmp_path / "watched.nt"
    src.write_text(bsbm_ntriples(40, seed=4))
    st, doc = req(server, "PUT", "/datasets/wds",
                  body=json.dumps({"source": os.fspath(src)}).encode())
    assert st == 201 and doc["source"] == os.fspath(src)

    def n_done():
        _, jl = req(server, "GET", "/datasets/wds/jobs")
        return sum(1 for j in jl["jobs"]
                   if j["state"] == "done" and j["trigger"] == "watch")

    deadline = time.time() + 60
    while n_done() < 1:
        assert time.time() < deadline, "watcher never assessed the source"
        time.sleep(0.05)
    with open(src, "a") as f:
        f.write(bsbm_ntriples(5, seed=44))
    while n_done() < 2:
        assert time.time() < deadline, "watcher missed the edit"
        time.sleep(0.05)
    edited = src.read_text()
    cold = qa.assess(edited, metrics="paper", base=BASE)
    _, rep = req(server, "GET", "/datasets/wds/report")
    assert rep["nTriples"] == cold.n_triples


# -- alerts --------------------------------------------------------------------

def test_alert_fires_on_regression_and_posts_webhook(server, tmp_path):
    clean = bsbm_ntriples(80, seed=6)
    doctored = clean + bsbm_ntriples(10, seed=66)
    v1 = qa.assess(clean, metrics="paper", base=BASE).values
    v2 = qa.assess(doctored, metrics="paper", base=BASE).values
    regressed = sorted(n for n in v1 if v2[n] < v1[n])
    assert regressed, "fixture data produced no metric regression"
    metric = regressed[0]

    # a tiny webhook sink
    import http.server
    hits = []

    class Sink(http.server.BaseHTTPRequestHandler):
        def do_POST(self):
            n = int(self.headers.get("Content-Length") or 0)
            hits.append(json.loads(self.rfile.read(n)))
            self.send_response(200)
            self.send_header("Content-Length", "0")
            self.end_headers()

        def log_message(self, *a):
            pass

    sink = http.server.HTTPServer(("127.0.0.1", 0), Sink)
    threading.Thread(target=sink.serve_forever, daemon=True).start()
    try:
        rules = [f"delta({metric}) < 0", f"{metric} > 2"]  # 2nd never fires
        st, doc = req(server, "PUT", "/datasets/al", body=json.dumps({
            "alerts": rules,
            "webhook": f"http://127.0.0.1:{sink.server_address[1]}/hook",
        }).encode())
        assert st == 201, doc

        j1 = wait_job(server, "al", upload(server, "al", clean))
        assert j1["state"] == "done" and j1["alerts_fired"] == 0
        j2 = wait_job(server, "al", upload(server, "al", doctored))
        assert j2["state"] == "done" and j2["alerts_fired"] == 1

        st, doc = req(server, "GET", "/datasets/al/alerts")
        assert st == 200 and len(doc["alerts"]) == 1
        rec = doc["alerts"][0]
        assert rec["metric"] == metric and rec["dataset"] == "al"
        assert rec["value"] == v2[metric] and rec["previous"] == v1[metric]
        assert rec["delta"] == v2[metric] - v1[metric] < 0
        assert hits and hits[0]["rule"] == f"delta({metric}) < 0"
        _, prom = req(server, "GET", "/metrics")
        assert 'repro_alerts_fired_total{dataset="al"} 1' in prom.decode()
    finally:
        sink.shutdown()
        sink.server_close()


def test_alert_rule_parsing():
    r = parse_rule("L1 < 0.9")
    assert (r.metric, r.op, r.bound, r.on_delta) == ("L1", "<", 0.9, False)
    d = parse_rule("delta(CN2_EXACT) <= -1e-3")
    assert (d.metric, d.on_delta, d.bound) == ("CN2_EXACT", True, -1e-3)
    assert d.evaluate({"CN2_EXACT": 0.5}, None) is None  # no baseline
    assert d.evaluate({"CN2_EXACT": 0.5}, {"CN2_EXACT": 0.6}) is not None
    for bad in ("", "L1", "L1 < ", "< 0.9", "L1 ~ 2", "delta L1 < 0",
                "L1 < x"):
        with pytest.raises(ValueError):
            parse_rule(bad)


# -- API hygiene ---------------------------------------------------------------

def test_name_validation_and_error_statuses(server):
    for bad in ("..", ".hidden", "a b", "a/b", "-x", "x" * 65, ""):
        with pytest.raises(RegistryError):
            validate_name(bad)
    st, doc = req(server, "PUT", "/datasets/..", body=b"{}")
    assert st == 400 and "invalid dataset name" in doc["error"]
    st, doc = req(server, "PUT", "/datasets/ok",
                  body=json.dumps({"alerts": ["L1 <"]}).encode())
    assert st == 400 and "bad alert rule" in doc["error"]
    st, doc = req(server, "GET", "/datasets/nope/report")
    assert st == 404
    st, doc = req(server, "PUT", "/datasets/empty/data", body=b"")
    assert st == 400 and "empty upload" in doc["error"]
    st, doc = req(server, "POST", "/datasets/nodata/assess")
    assert st == 404                      # never registered
    st, _ = req(server, "PUT", "/datasets/nodata", body=b"")
    assert st == 201
    st, doc = req(server, "POST", "/datasets/nodata/assess")
    assert st == 409 and "no data" in doc["error"]
    st, doc = req(server, "GET", "/datasets/nodata/jobs/999")
    assert st == 404
    st, doc = req(server, "POST", "/healthz")
    assert st == 405


def test_registry_survives_daemon_restart(server, tmp_path):
    data = bsbm_ntriples(50, seed=7)
    job = wait_job(server, "persist", upload(server, "persist", data))
    assert job["state"] == "done"
    root = server.registry.root
    server.close()

    srv2 = QAServer(ServerConfig(store_root=root, metrics="paper",
                                 base=BASE, segment_bytes=SEG,
                                 watch=False), port=0).start()
    try:
        st, doc = req(srv2, "GET", "/datasets")
        assert [d["name"] for d in doc["datasets"]] == ["persist"]
        # reports and history are durable; job log is in-memory only
        st, rep = req(srv2, "GET", "/datasets/persist/report")
        assert st == 200 and rep["nTriples"] == \
            qa.assess(data, metrics="paper", base=BASE).n_triples
        st, hist = req(srv2, "GET", "/datasets/persist/history")
        assert hist["snapshots"] == 1
        # a re-assessment of the same bytes is pure reuse of the old
        # daemon's store
        st, doc = req(srv2, "POST", "/datasets/persist/assess")
        assert st == 202
        job2 = wait_job(srv2, "persist", doc["job"]["id"])
        assert job2["state"] == "done"
        assert job2["exec_stats"]["segments_rescanned"] == 0
    finally:
        srv2.close()


# -- backpressure: bounded job queue -> 429 + Retry-After ----------------------

def test_queue_full_returns_429_with_retry_after(tmp_path):
    """Once max_queued jobs are waiting, job-enqueuing endpoints answer
    429 with a Retry-After header, count the rejection in
    repro_jobs_rejected_total, and recover after the queue drains."""
    srv = QAServer(ServerConfig(
        store_root=os.fspath(tmp_path / "root"), metrics="paper",
        base=BASE, workers=1, segment_bytes=SEG, watch=False,
        max_queued=1), port=0).start()
    release = threading.Event()
    started = threading.Event()

    def blocking(job):
        started.set()
        assert release.wait(60)
    srv._execute = blocking           # job body: park the only worker
    try:
        data = bsbm_ntriples(5, seed=1).encode()
        st, _ = req(srv, "PUT", "/datasets/bp/data", body=data)
        assert st == 202
        assert started.wait(30)       # worker occupied
        st, _ = req(srv, "PUT", "/datasets/bp/data", body=data)
        assert st == 202              # 1 waiting == max_queued

        r = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/datasets/bp/data", data=data,
            method="PUT")
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(r, timeout=30)
        assert exc.value.code == 429
        retry_after = exc.value.headers.get("Retry-After")
        assert retry_after is not None and int(retry_after) >= 1
        body = json.loads(exc.value.read())
        assert "queue full" in body["error"]

        st, text = req(srv, "GET", "/metrics")
        assert ('repro_jobs_rejected_total{dataset="bp"} 1'
                in text.decode())

        # POST /assess hits the same bound
        st, doc = req(srv, "POST", "/datasets/bp/assess")
        assert st == 429, doc

        release.set()                 # drain; submissions work again
        deadline = time.time() + 30
        while srv.jobs.counts()["queued"] + srv.jobs.counts()["running"]:
            assert time.time() < deadline
            time.sleep(0.05)
        st, _ = req(srv, "PUT", "/datasets/bp/data", body=data)
        assert st == 202
    finally:
        release.set()
        srv.close()
