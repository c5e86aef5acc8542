"""Daemon smoke for CI / scripts/check.sh: start the service on an
ephemeral port, upload a small N-Triples file, poll the job to
completion, assert the DQV report parses and /metrics exposes nonzero
assessment counters, then shut down cleanly.

  PYTHONPATH=src python scripts/serve_smoke.py

Chaos mode (``--chaos``) exercises the durability plane end to end: a
daemon subprocess accepts three uploads and is hard-killed by an
injected crash point right after journaling the second job's start; a
restarted daemon must replay and complete every accepted job (zero lost
jobs, values identical to a direct ``qa.assess``), retry a transiently-
failing job, count a webhook that never answers, reclaim a dataset via
DELETE, and exit 0 on SIGTERM.

  PYTHONPATH=src python scripts/serve_smoke.py --chaos

(``--chaos-daemon ROOT PORTFILE PHASE`` and ``--chaos-reference`` are
the internal subprocess entry points.  The orchestrating parent never
touches JAX itself: on an accelerator only one process at a time may hold
the device, so the reference values come from a child that exits before
the first daemon starts.)
"""
import json
import os
import sys
import tempfile
import time
import urllib.error
import urllib.request

from repro.rdf import bsbm_ntriples
from repro.serve import QAServer, ServerConfig

BASE = ("http://bsbm.example.org/",)
SRC = os.path.abspath(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "..", "src"))


def main() -> None:
    root = tempfile.mkdtemp(prefix="qa-serve-smoke-")
    srv = QAServer(ServerConfig(store_root=root, metrics="paper",
                                base=BASE, segment_bytes=16384),
                   port=0).start()
    api = f"http://127.0.0.1:{srv.port}"
    try:
        data = bsbm_ntriples(300, seed=0).encode()
        req = urllib.request.Request(f"{api}/datasets/smoke/data",
                                     data=data, method="PUT")
        with urllib.request.urlopen(req, timeout=60) as resp:
            assert resp.status == 202, resp.status
            job = json.load(resp)["job"]

        deadline = time.time() + 300
        while True:
            with urllib.request.urlopen(
                    f"{api}/datasets/smoke/jobs/{job['id']}",
                    timeout=30) as resp:
                j = json.load(resp)
            if j["state"] in ("done", "failed"):
                break
            assert time.time() < deadline, "smoke job timed out"
            time.sleep(0.2)
        assert j["state"] == "done", f"job failed: {j['error']}"
        assert j["exec_stats"]["bytes_total"] == len(data)

        with urllib.request.urlopen(f"{api}/datasets/smoke/report",
                                    timeout=30) as resp:
            rep = json.load(resp)
        assert rep["measurements"], "DQV report has no measurements"
        assert rep["execStats"]["bytes_rescanned"] == len(data)
        with urllib.request.urlopen(
                f"{api}/datasets/smoke/report?format=nt",
                timeout=30) as resp:
            assert resp.read().count(b"QualityMeasurement") == 0  # NT body
        with urllib.request.urlopen(f"{api}/healthz", timeout=30) as resp:
            assert json.load(resp)["status"] == "ok"
        with urllib.request.urlopen(f"{api}/metrics", timeout=30) as resp:
            prom = resp.read().decode()
        want = 'repro_assessments_total{dataset="smoke",state="done"} 1'
        assert want in prom, f"missing assessment counter:\n{prom}"
        assert 'repro_http_requests_total' in prom
        print(f"serve smoke OK: job {job['id']} done, "
              f"{len(rep['measurements'])} measurements, "
              f"{j['exec_stats']['segments_rescanned']} segments scanned")
    finally:
        srv.close()


def _req(api, method, path, body=None, timeout=60):
    """(status, parsed JSON); 4xx/5xx return instead of raising."""
    r = urllib.request.Request(api + path, data=body, method=method)
    try:
        with urllib.request.urlopen(r, timeout=timeout) as resp:
            return resp.status, json.load(resp)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


def _wait_done(api, name, job_id, deadline):
    while True:
        st, j = _req(api, "GET", f"/datasets/{name}/jobs/{job_id}")
        assert st == 200, (st, j)
        if j["state"] in ("done", "failed"):
            return j
        assert time.time() < deadline, f"job {job_id} stuck: {j}"
        time.sleep(0.1)


def chaos_daemon(argv) -> int:
    """Internal: one service daemon under fault injection.  Phase
    ``crash`` hard-kills itself (``os._exit``) right after the journal
    append for the second job start; phase ``clean`` replays the journal
    but fails dataset c2's first attempt transiently."""
    import signal

    from repro.serve import ServiceFaultInjector
    root, portfile, phase = argv
    if phase == "crash":
        faults = ServiceFaultInjector(slow_jobs={"c1": 1.0},
                                      crash_after_journal={"start#2"},
                                      fail_webhooks=-1)
    else:
        faults = ServiceFaultInjector(fail_jobs={"c2": 1})
    srv = QAServer(ServerConfig(store_root=root, metrics="paper",
                                base=BASE, workers=1,
                                segment_bytes=16384, watch=False,
                                retry_base=0.05, webhook_retries=2,
                                webhook_backoff=0.05),
                   port=0, faults=faults).start()
    signal.signal(signal.SIGTERM, lambda s, f: srv.request_stop())
    with open(portfile + ".tmp", "w") as f:
        f.write(str(srv.port))
    os.replace(portfile + ".tmp", portfile)
    srv.wait()
    srv.close()
    print("# chaos daemon: clean shutdown", flush=True)
    return 0


def _chaos_data() -> dict:
    return {f"c{i}": bsbm_ntriples(120, seed=i) for i in (1, 2, 3)}


def chaos_reference() -> int:
    """Internal: print the direct ``qa.assess`` values of every chaos
    dataset as one JSON line."""
    from repro import qa
    print(json.dumps({name: {k: float(v) for k, v in sorted(
        qa.assess(text, metrics="paper", base=BASE).values.items())}
        for name, text in _chaos_data().items()}))
    return 0


def chaos() -> None:
    """Orchestrate the crash/replay cycle and gate on zero lost jobs."""
    import shutil
    import signal
    import subprocess

    env = {**os.environ, "PYTHONPATH": SRC}
    refs = json.loads(subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--chaos-reference"],
        env=env, capture_output=True, text=True, check=True,
        timeout=300).stdout.splitlines()[-1])
    root = tempfile.mkdtemp(prefix="qa-serve-chaos-")
    portfile = os.path.join(root, ".port")
    data = _chaos_data()
    procs = []

    def spawn(phase):
        if os.path.exists(portfile):
            os.remove(portfile)
        p = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--chaos-daemon", root, portfile, phase], env=env)
        procs.append(p)
        deadline = time.time() + 180
        while not os.path.exists(portfile):
            assert p.poll() is None, \
                f"chaos daemon died at startup (rc={p.returncode})"
            assert time.time() < deadline, "chaos daemon never came up"
            time.sleep(0.05)
        with open(portfile) as f:
            return p, f"http://127.0.0.1:{int(f.read())}"

    try:
        p1, api = spawn("crash")
        # c1 carries an always-firing alert + a webhook nobody answers
        st, _ = _req(api, "PUT", "/datasets/c1", body=json.dumps(
            {"alerts": ["L1 >= 0"],
             "webhook": "http://127.0.0.1:9/hook"}).encode())
        assert st == 201, st
        job_ids = {}
        for name, text in data.items():
            st, doc = _req(api, "PUT", f"/datasets/{name}/data",
                           body=text.encode())
            assert st == 202, (name, st, doc)
            job_ids[name] = doc["job"]["id"]
        # the injected crash point fires after journaling start#2 —
        # an in-process stand-in for kill -9 mid-queue
        rc = p1.wait(timeout=300)
        assert rc == 17, f"expected injected crash exit 17, got {rc}"

        p2, api = spawn("clean")
        deadline = time.time() + 300
        lost = []
        for name in ("c2", "c3"):       # c1 finished before the crash
            j = _wait_done(api, name, job_ids[name], deadline)
            if j["state"] != "done":
                lost.append((name, j["error"]))
                continue
            assert j["values"] == refs[name], name
        assert not lost, f"jobs lost across the crash: {lost}"
        # c2's replay was also made transiently flaky: retried once
        st, j2 = _req(api, "GET", f"/datasets/c2/jobs/{job_ids['c2']}")
        assert j2["attempts"] == 2, j2["attempts"]
        # c1's pre-crash report survived on disk
        st, rep = _req(api, "GET", "/datasets/c1/report")
        assert st == 200 and rep["measurements"]
        # re-assessing c1 fires the alert again; the dead webhook is
        # retried then counted, never fatal
        st, doc = _req(api, "POST", "/datasets/c1/assess")
        assert st == 202, (st, doc)
        j1 = _wait_done(api, "c1", doc["job"]["id"], deadline)
        assert j1["state"] == "done" and j1["alerts_fired"] >= 1
        st, _ = _req(api, "GET", "/healthz")
        assert st == 200
        with urllib.request.urlopen(f"{api}/metrics", timeout=30) as r:
            prom = r.read().decode()
        for want in ('repro_jobs_replayed_total{dataset="c2"} 1',
                     'repro_jobs_replayed_total{dataset="c3"} 1',
                     'repro_job_retries_total{dataset="c2"} 1',
                     'repro_webhook_failures_total{dataset="c1"} 1'):
            assert want in prom, f"missing {want!r} in /metrics"
        # lifecycle GC: DELETE reclaims the tenant's whole footprint
        st, doc = _req(api, "DELETE", "/datasets/c3")
        assert st == 200 and doc["bytes_reclaimed"] > 0, (st, doc)
        assert not os.path.exists(os.path.join(root, "c3"))
        st, _ = _req(api, "GET", "/datasets/c3")
        assert st == 404
        # graceful shutdown: SIGTERM drains and exits 0
        p2.send_signal(signal.SIGTERM)
        rc = p2.wait(timeout=120)
        assert rc == 0, f"SIGTERM exit code {rc}"
        print("serve chaos OK: 3 jobs accepted, crash after start#2, "
              "2 replayed (1 via retry), 0 lost, webhook failure "
              "counted, DELETE reclaimed, SIGTERM exit 0")
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        shutil.rmtree(root, ignore_errors=True)


if __name__ == "__main__":
    if "--chaos-daemon" in sys.argv:
        i = sys.argv.index("--chaos-daemon")
        sys.exit(chaos_daemon(sys.argv[i + 1:i + 4]))
    elif "--chaos-reference" in sys.argv:
        sys.exit(chaos_reference())
    elif "--chaos" in sys.argv:
        sys.exit(chaos())
    else:
        sys.exit(main())
