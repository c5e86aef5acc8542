"""The readers of the program's spans and counters, on hand-made
recorders, and on steps whose answers carry none (a program that records
no spans): there each reader gives None."""
import types

import pytest

import run as harness
from repro.spans import Recorder, Span

PEAKS = harness.load_json(harness.HERE, "peaks.json")["devices"][
    "TPU v5 lite"]
DECLARED = ("ingest_self_triples_per_s", "ingest_fallback_share",
            "segment_s_per_update", "replay_s_per_update",
            "rescan_ingest_s_per_update", "freeze_s_per_update")
# Read only in the traced run, these two would measure the profiler: it
# stretches the host-to-device copy about 19 times on a TPU v5e.  They
# stay out of BENCHMARK.json until the harness reads program counters in
# an untraced run.
HELD_BACK = ("h2d_bytes_per_s", "finalize_share.reassess")
NEW = DECLARED + HELD_BACK
S = 1_000_000_000           # ns in a second


def recorder(spans, counts=None):
    """A run's record: the root from 0 to 10 s, then ``(name, start_s,
    end_s, parent)``."""
    rec = Recorder()
    rec.spans.append(Span("qa.run", 0, 10 * S, -1, 1))
    for name, a, b, parent in spans:
        rec.spans.append(Span(name, int(a * S), int(b * S), parent, 2))
    rec.counts.update(counts or {})
    return rec


def make_run(steps, window_s=10.0):
    run = harness.Run(PEAKS)
    run.steps, run.window_s = steps, window_s
    return run


def step(rec, triples=0):
    return {"triples": triples, "latency_s": 10.0,
            "answer": types.SimpleNamespace(trace=rec)}


def read(name, run):
    return harness.load_module("metrics", name).read(run)


def test_ingest_self_time_excludes_children_and_sums_over_steps():
    a = recorder([("ingest.read", 0, 1, 0), ("ingest.tokenize", 1, 3, 0),
                  ("compile", 2, 2.5, 2), ("scan.wait", 3, 9, 0)])
    b = recorder([("ingest.fallback", 0, 1.5, 0)])
    run = make_run([step(a, 3000), step(b, 1000)])
    # 1 + (2 - 0.5) + 1.5 = 4 s of ingest for 4000 triples
    assert read("ingest_self_triples_per_s", run) == pytest.approx(1000.0)


def test_fallback_share_and_transfer_rate_read_the_counters():
    a = recorder([("scan.transfer", 0, 2, 0)],
                 {"ingest.lines": 900, "ingest.fallback_lines": 90,
                  "transfer.bytes": 4000})
    b = recorder([("scan.transfer", 0, 2, 0)],
                 {"ingest.lines": 100, "ingest.fallback_lines": 10,
                  "transfer.bytes": 4000})
    run = make_run([step(a), step(b)])
    assert read("ingest_fallback_share", run) == pytest.approx(0.1)
    assert read("h2d_bytes_per_s", run) == pytest.approx(2000.0)


def test_finalize_share_is_merge_and_finalize_over_the_window():
    a = recorder([("scan.merge", 0, 1, 0), ("scan.finalize", 1, 2, 0),
                  ("compile", 1, 1.5, 2), ("scan.wait", 2, 3, 0)])
    run = make_run([step(a), step(a)], window_s=20.0)
    assert read("finalize_share.reassess", run) == pytest.approx(0.15)


def test_store_seconds_per_changeset():
    a = recorder([("store.segment", 0, 1, 0), ("store.fingerprint", 1, 2, 0),
                  ("store.replay", 2, 3, 0), ("ingest.tokenize", 3, 4, 0),
                  ("store.freeze", 4, 4.5, 0), ("store.commit", 5, 5.25, 0),
                  ("store.history", 6, 6.25, 0)])
    b = recorder([("store.segment", 0, 2, 0)])
    run = make_run([step(a), step(b)])
    assert read("segment_s_per_update", run) == pytest.approx(2.0)
    assert read("replay_s_per_update", run) == pytest.approx(0.5)
    assert read("rescan_ingest_s_per_update", run) == pytest.approx(0.5)
    assert read("freeze_s_per_update", run) == pytest.approx(0.5)


@pytest.mark.parametrize("name", NEW)
def test_no_spans_recorded_gives_none(name):
    untraced = {"triples": 10, "latency_s": 1.0,
                "answer": types.SimpleNamespace()}
    assert read(name, make_run([])) is None
    assert read(name, make_run([untraced])) is None
    # a record without the reader's spans: no rate, no share of nothing;
    # a time per changeset or a share of the window is 0
    assert read(name, make_run([step(recorder([]))])) in (None, 0.0)


def test_every_new_reader_is_declared_with_its_cell():
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name in DECLARED:
        m = declared[name]
        assert m["source"] == "program_counter"
        assert len(m["workloads"]) == 1
    assert not set(HELD_BACK) & set(declared)
