"""Each metric reader on hand-made steps, counters and traces, and the
benchmark's declaration against the files that serve it."""
import os
import re
import types

import pytest

import run as harness
from trace_reduce import Event, Trace

BENCH = harness.load_json(harness.ROOT, "BENCHMARK.json")
PEAKS = harness.load_json(harness.HERE, "peaks.json")["devices"][
    "TPU v5 lite"]


def stats(**kw):
    base = dict(wall_seconds=0.0, chunk_eval_seconds=[], bytes_rescanned=0,
                footprints_replayed=0)
    return types.SimpleNamespace(**dict(base, **kw))


def make_run(steps, window_s=10.0, extra=None, trace=None):
    run = harness.Run(PEAKS)
    run.steps, run.window_s = steps, window_s
    run.extra, run.trace = extra or {}, trace
    return run


def read(name, run):
    return harness.load_module("metrics", name).read(run)


def trace(ops, window=(0.0, 1e9), modules=(), spans=()):
    return Trace(window, {"/device:TPU:0": [Event(*o) for o in ops]},
                 {"/device:TPU:0": [Event(*m) for m in modules]},
                 [Event(*s) for s in spans])


def test_assess_triples_per_s_counts_every_triple_over_the_window():
    steps = [{"triples": 1000}, {"triples": 1000}, {"triples": 500}]
    assert read("assess_triples_per_s", make_run(steps, 5.0)) == 500.0
    assert read("assess_triples_per_s", make_run([], 5.0)) is None


def test_update_seconds_and_p90():
    steps = [{"latency_s": float(x)} for x in range(1, 11)]
    assert read("update_s", make_run(steps, 60.0)) == 6.0
    assert read("update_p90_s", make_run(steps)) == pytest.approx(9.1)
    assert read("update_p90_s", make_run([])) is None


def test_ingest_triples_per_s():
    run = make_run([], extra={"ingest_s": 4.0, "ingest_triples": 1000})
    assert read("ingest_triples_per_s", run) == 250.0
    assert read("ingest_triples_per_s", make_run([])) is None


def test_host_blocked_share_sums_over_assessments():
    steps = [{"stats": stats(wall_seconds=10.0,
                             chunk_eval_seconds=[0.1, 0.4])},
             {"stats": stats(wall_seconds=10.0, chunk_eval_seconds=[0.5])},
             {"stats": None}]
    assert read("host_blocked_share", make_run(steps)) == 0.05
    assert read("host_blocked_share", make_run([{"stats": None}])) is None


def test_store_counters():
    steps = [{"changed_bytes": 100, "stats": stats(bytes_rescanned=1000,
                                                   footprints_replayed=20)},
             {"changed_bytes": 300, "stats": stats(bytes_rescanned=3000,
                                                   footprints_replayed=30)}]
    run = make_run(steps)
    assert read("rescan_bytes_per_changed_byte", run) == 10.0
    assert read("footprints_replayed_per_update", run) == 25.0


def test_idle_share_is_one_minus_the_op_union_over_the_window():
    t = trace([("a", 0.0, 2e8), ("b", 1e8, 3e8), ("c", 9e8, 1.2e9)])
    run = make_run([], trace=t)
    for name in ("device_idle_share.bulk", "device_idle_share.update"):
        assert read(name, run) == pytest.approx(0.6)
    assert read("device_idle_share.bulk", make_run([])) is None


def test_scan_roofline_is_the_least_time_over_the_pass_time():
    rows = 1 << 20
    least = rows * 52 / PEAKS["hbm_bytes_per_s"]
    t = trace([("fusion", 0.0, 1e6)],
              modules=[("jit_local_pass(1)", 0.0, 10 * least * 1e9),
                       ("jit_local_pass(1)", 5e8, 5e8 + 10 * least * 1e9),
                       ("jit_other", 0.0, 1e8)])
    run = make_run([], extra={"rows_per_scan": rows}, trace=t)
    assert read("scan_roofline", run) == pytest.approx(10.0)
    # a reader that finds nothing to read returns nothing, never 0
    assert read("scan_roofline", make_run([], extra={"rows_per_scan": 1},
                                          trace=trace([]))) is None
    assert read("scan_roofline", make_run([])) is None


def test_breakdown_names_gaps_by_the_host_span():
    t = trace([("%x = f32[8]{0} negate(f32[8]{0} %a)", 0.0, 1e8),
               ("y", 6e8, 7e8)],
              modules=[("jit_p(123)", 0.0, 1e8)],
              spans=[("bench.window", 0.0, 1e9),
                     ("bench.assess", 0.0, 1e9),
                     ("bench.changeset.write", 2e8, 5e8)])
    b = t.breakdown()
    assert b["device_ops"] == [["jit_p %x negate", 0.1],
                               ["? y", pytest.approx(0.1)]]
    assert b["idle_gaps"][0] == ["bench.changeset.write",
                                 pytest.approx(0.5)]
    assert b["idle_gaps"][1] == ["bench.assess", pytest.approx(0.3)]
    assert t.busy_s() == pytest.approx(0.2)


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_every_metric_has_a_reader_and_every_cell_its_metrics():
    cells = {w["name"] for w in BENCH["workloads"]}
    layers = set()
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]), m["name"]
        if m["name"] != "setup_s":
            assert os.path.exists(os.path.join(
                harness.HERE, "metrics", m["name"] + ".py")), m["name"]
        assert set(m.get("workloads", cells)) <= cells
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        layers.add(m["layer"])
        moved = e2e[m["moves"]]
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))
    for c in cells:
        assert len(harness.cell_metrics(BENCH, c, False)) >= 2
        assert harness.cell_metrics(BENCH, c, True)
    for w in BENCH["workloads"]:
        assert os.path.exists(os.path.join(
            harness.HERE, "configs", w["config"] + ".json"))
        assert os.path.exists(os.path.join(
            harness.HERE, "traffic", w["traffic"] + ".json"))
