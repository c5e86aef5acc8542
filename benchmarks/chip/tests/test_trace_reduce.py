"""The trace reduction against a trace recorded once on a TPU v5e
(``tests/record_trace.py``: a streamed assessment of 2,000 BSBM products
and one ``qa.assess`` of 2^16 encoded triples, each in a ``bench.assess``
span)."""
import os

import pytest

import trace_reduce

FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "trace.xplane.pb.gz")


@pytest.fixture(scope="module")
def trace():
    return trace_reduce.load_gz(FIXTURE)


def test_planes_and_spans(trace):
    assert sorted(trace.ops) == ["/device:TPU:0"]
    assert len(trace.ops["/device:TPU:0"]) == 68
    assert len(trace.modules["/device:TPU:0"]) == 68
    assert [s.name for s in trace.spans] == ["bench.assess"] * 2


def test_busy_and_idle(trace):
    # no bench.window span in this trace: the window is its whole extent
    assert trace.window_s() == pytest.approx(0.089239669)
    assert trace.busy_s() == pytest.approx(0.001152694)
    assert trace.idle_share() == pytest.approx(1 - 0.001152694 / 0.089239669)


def test_pass_executables(trace):
    seconds, runs = trace.module_time(("jit_local_pass",))
    assert runs == 4
    assert seconds == pytest.approx(0.001137542)
    assert trace.module_time(("jit_no_such_pass",)) == (0.0, 0)


def test_breakdown_names_ops_by_executable_and_gaps_by_span(trace):
    b = trace.breakdown()
    assert len(b["device_ops"]) == trace_reduce.TOP
    top, seconds = b["device_ops"][0]
    # the HyperLogLog scatter-max is a custom fusion of the pass
    assert top == "jit_local_pass %fusion.1 fusion/kCustom"
    assert seconds == pytest.approx(0.000511532)
    assert [n for n, _ in b["idle_gaps"]] == ["bench.assess"] * 10
    assert b["idle_gaps"][0][1] == pytest.approx(0.042905532)


def test_op_label():
    assert trace_reduce.op_label(
        "%neg.1 = f32[4096]{0:T(1024)} negate(f32[4096]{0:T(1024)} %x.1)"
    ) == "%neg.1 negate"
    assert trace_reduce.op_label(
        "%f = (pred[8]{0:T(1024)}, s32[]{:T(128)}) fusion(s32[8,13]{0,1} %p),"
        " kind=kLoop, calls=%c") == "%f fusion/kLoop"
