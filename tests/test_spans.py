"""The program's spans and counters (``repro.spans``): nesting, parents
and self time, the pipelined producer's spans in the run that started
it, the same spans in a profiler trace on the same clock, a bounded span
count per chunk, the ingest counters, and the scheduler's statistics
read from the spans that cover the same intervals."""
import glob
import os
import threading

import jax
import jax.numpy as jnp
import pytest

from repro import qa, spans
from repro.core.evaluator import QualityEvaluator
from repro.rdf import bsbm_ntriples, ingest
from repro.spans import Recorder, Span

DIRTY = os.path.join(os.path.dirname(__file__), "data", "dirty.nt")


def named(rec, name):
    return [s for s in rec.spans if s.name == name]


def seconds(s):
    return (s.end_ns - s.start_ns) / 1e9


def test_nesting_parents_and_self_time():
    with spans.run() as rec:
        with spans.span("a"):
            with spans.span("b"):
                pass
        with spans.span("c") as c:
            spans.count("things", 3)
            spans.count("things", 4)
    assert [(s.name, s.parent) for s in rec.spans] == [
        ("qa.run", -1), ("a", 0), ("b", 1), ("c", 0)]
    assert all(s.end_ns >= s.start_ns for s in rec.spans)
    assert rec.counts == {"things": 7}
    assert c.seconds == (c.end_ns - c.start_ns) / 1e9 >= 0

    # self time: the duration less the union of the children's intervals,
    # children on other threads included, clipped to the parent
    hand = Recorder()
    for s in [Span("qa.run", 0, 100, -1, 1), Span("x.a", 10, 40, 0, 1),
              Span("x.b", 30, 60, 0, 2), Span("x.c", 90, 120, 0, 2),
              Span("x.d", 15, 20, 1, 1)]:
        hand.spans.append(s)
    # 100 less [10, 60] and [90, 100]
    assert hand.self_seconds("qa.run") == pytest.approx(40e-9)
    assert hand.self_seconds("x.a") == pytest.approx(25e-9)
    assert hand.self_seconds("x.") == pytest.approx(90e-9)
    assert hand.self_seconds_by_name() == pytest.approx(
        {"qa.run": 40e-9, "x.a": 25e-9, "x.b": 30e-9, "x.c": 30e-9,
         "x.d": 5e-9})


def test_outside_a_run_spans_time_themselves_and_record_nothing():
    with spans.span("alone") as s:
        spans.count("nobody", 1)
    assert s.seconds >= 0
    assert spans.handle() == (None, -1)


def test_nested_entries_share_one_root(tmp_path):
    text = bsbm_ntriples(300, seed=2)
    res = qa.pipeline().metrics("paper").incremental(
        os.fspath(tmp_path / "st"), segment_bytes=4096).run(text)
    assert [s.name for s in res.trace.spans].count("qa.run") == 1
    # the wall is the assessment up to the commit, as it always was
    [assess] = named(res.trace, "store.assess")
    assert res.exec_stats.wall_seconds == seconds(assess)
    for late in ("store.commit", "store.history"):
        [s] = named(res.trace, late)
        assert s.start_ns >= assess.end_ns and s.parent == 0
    names = {s.name for s in res.trace.spans}
    assert {"store.segment", "store.fingerprint", "store.load_state",
            "store.freeze", "store.commit", "store.history",
            "ingest.tokenize", "scan.eval", "scan.finalize"} <= names


def test_compile_is_a_child_of_the_compiling_span():
    fn = jax.jit(lambda x: jnp.cumsum(x * 3 + 1))      # never compiled yet
    with spans.run() as rec:
        with spans.span("step"):
            fn(jnp.arange(7.0)).block_until_ready()
    step = [i for i, s in enumerate(rec.spans) if s.name == "step"][0]
    compiles = named(rec, spans.COMPILE)
    assert compiles and all(s.parent == step for s in compiles)
    assert all(s.end_ns > s.start_ns for s in compiles)


def test_producer_thread_spans_belong_to_their_run(tmp_path):
    """Two pipelined runs at once, each on its own thread with its own
    producer: each recorder holds its own producer's ingest spans and
    counts, parented to its own root, and nothing of the other run."""
    sizes = {"a": 1500, "b": 2600}
    results, errors = {}, []

    def go(key):
        try:
            text = bsbm_ntriples(sizes[key], seed=7)
            path = tmp_path / f"{key}.nt"
            path.write_text(text)
            results[key] = (qa.pipeline().metrics("paper")
                            .streamed(512).pipelined().run(str(path)))
        except Exception as e:      # noqa: BLE001 — reported below
            errors.append(e)

    threads = [threading.Thread(target=go, args=(k,)) for k in sizes]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
        assert not t.is_alive()
    assert not errors, errors
    for key, res in results.items():
        rec = res.trace
        lines = len(bsbm_ntriples(sizes[key], seed=7).splitlines())
        assert rec.counts["ingest.lines"] == lines
        root_thread = rec.spans[0].thread
        produced = [s for s in rec.spans if s.name.startswith("ingest.")
                    or s.name == "scan.transfer"]
        assert produced
        assert all(s.thread != root_thread for s in produced)
        # the producer's top spans are children of the scheduling span
        # that started it, on the consumer's thread
        tops = [s for s in produced
                if rec.spans[s.parent].name == "scan.schedule"]
        assert {s.name for s in tops} >= {"ingest.read", "scan.transfer",
                                          "ingest.tokenize"}
        assert all(rec.spans[s.parent].thread == root_thread
                   for s in tops)
        assert len(named(rec, "scan.wait")) == res.exec_stats.chunks_total
        # the consumer waited on the producer, and the spans say so
        assert named(rec, "scan.feed_wait")


def _host_repro_events(trace_dir):
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)
    assert len(path) == 1, path
    pd = jax.profiler.ProfileData.from_file(path[0])
    out = []
    for plane in pd.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                out.extend((e.name[len(spans.PREFIX):], e.start_ns,
                            e.start_ns + e.duration_ns)
                           for e in line.events
                           if e.name.startswith(spans.PREFIX))
    return sorted(out, key=lambda e: (e[1], -e[2]))


def test_profiler_trace_holds_the_same_spans_on_the_same_clock(tmp_path):
    text = bsbm_ntriples(2000, seed=4)
    pipe = qa.pipeline().metrics("paper").streamed(700)
    pipe.run(text)                  # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(os.fspath(tmp_path), profiler_options=opts)
    try:
        res = pipe.run(text)
    finally:
        jax.profiler.stop_trace()
    rec = res.trace.spans
    assert not named(res.trace, spans.COMPILE)
    order = sorted(range(len(rec)),
                   key=lambda i: (rec[i].start_ns, -rec[i].end_ns))
    events = _host_repro_events(os.fspath(tmp_path))
    assert [e[0] for e in events] == [rec[i].name for i in order]
    # the same clock: one offset between the record and the trace, to
    # within the time it takes to enter an annotation
    offsets = [rec[i].start_ns - e[1] for i, e in zip(order, events)]
    assert max(offsets) - min(offsets) < 2_000_000
    # the same nesting: each span's event lies inside its parent's
    where = dict(zip(order, events))
    for i, s in enumerate(rec):
        if s.parent >= 0:
            _, a, b = where[i]
            _, pa, pb = where[s.parent]
            assert pa <= a and b <= pb, (s, rec[s.parent])


def test_span_count_is_bounded_per_chunk():
    text = bsbm_ntriples(20_000, seed=1)
    res = qa.pipeline().metrics("paper").streamed(1024).run(text)
    rec = res.trace
    chunks = res.exec_stats.chunks_total
    lines = len(text.splitlines())
    assert chunks >= 20 and rec.counts["ingest.lines"] == lines
    assert len(rec.spans) <= 32 * chunks
    assert len(rec.spans) < lines / 50


def test_fallback_lines_count_the_reference_parser_calls(monkeypatch):
    calls = []
    legacy = ingest.parse_ntriples

    def counting(text):
        calls.append(text)
        return legacy(text)

    monkeypatch.setattr(ingest, "parse_ntriples", counting)
    with open(DIRTY, "rb") as f:
        data = f.read()
    with spans.run() as rec:
        ingest.parse_encode(data)
    assert calls
    assert rec.counts["ingest.fallback_lines"] == len(calls)
    assert rec.counts["ingest.bytes"] == len(data)
    assert 0 < rec.counts["ingest.fallback_lines"] < rec.counts[
        "ingest.lines"]


def test_wide_tokens_count_long_tokens_off_the_reference_parser(monkeypatch):
    def refuse(text):
        raise AssertionError(f"reference parser called on {text[:40]!r}")

    monkeypatch.setattr(ingest, "parse_ntriples", refuse)
    # tokens of 129 B to 2 KB, some repeated, among short ones
    tokens = [f'"{"r" * (n - 2)}"' for n in range(129, 2049, 101)]
    tokens += [f"<http://example.org/{'i' * (n - 21)}>" for n in (129, 700)]
    lines = [f"<http://s> <http://p> {t} .\n" for t in tokens + tokens[:3]]
    lines += ["<http://s> <http://p> <http://o> .\n",
              f"{tokens[-1]} <http://p> {tokens[-2]} .\n"]
    data = "".join(lines).encode()
    with spans.run() as rec:
        ingest.parse_encode(data)
    long_occurrences = sum(len(t) > 128 for ln in lines
                           for t in ln[:-3].split(" "))
    assert long_occurrences == len(tokens) + 3 + 2
    assert rec.counts["ingest.fallback_lines"] == 0
    assert rec.counts["ingest.wide_tokens"] == long_occurrences


def test_single_shot_results_carry_a_trace():
    text = bsbm_ntriples(400, seed=9)
    res = qa.assess(text, metrics="paper")
    assert res.exec_stats is None
    names = [s.name for s in res.trace.spans]
    assert names[0] == "qa.run"
    for n in ("ingest.tokenize", "scan.transfer", "scan.dispatch",
              "scan.wait", "scan.merge", "scan.finalize"):
        assert n in names
    tt = ingest.parse_encode(text)
    ev = QualityEvaluator(["L1"])
    again = ev.assess(tt)
    assert again.trace.counts["transfer.bytes"] == tt.planes.nbytes
    assert again.trace.self_seconds("scan.transfer") > 0


@pytest.mark.parametrize("mode", ["streamed", "pipelined", "chunks"])
def test_scheduler_statistics_are_read_from_the_spans(mode):
    pipe = qa.pipeline().metrics("paper")
    pipe = {"streamed": lambda: pipe.streamed(500),
            "pipelined": lambda: pipe.streamed(500).pipelined(),
            "chunks": lambda: pipe.chunked(6)}[mode]()
    res = pipe.run(bsbm_ntriples(3000, seed=3))
    stats, rec = res.exec_stats, res.trace
    per_chunk = named(rec, "scan.wait" if mode == "pipelined"
                      else "scan.eval")
    assert stats.chunk_eval_seconds == [seconds(s) for s in per_chunk]
    # the wall is the scheduler's own run, finalize excluded, whatever
    # the entry did before it
    [sched] = named(rec, "scan.schedule")
    assert stats.wall_seconds == seconds(sched)
    assert sum(stats.chunk_eval_seconds) < stats.wall_seconds
    [final] = [s for s in named(rec, "scan.finalize") if s.parent == 0]
    assert final.start_ns >= sched.end_ns
    ingest_spans = [s for s in rec.spans if s.name.startswith("ingest.")]
    assert ingest_spans
    if mode == "chunks":
        # the whole file is parsed before the scheduler starts, outside
        # its wall
        assert all(s.end_ns <= sched.start_ns for s in ingest_spans)
    else:
        assert all(s.start_ns >= sched.start_ns for s in ingest_spans)
