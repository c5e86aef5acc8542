"""The resident four-chip cell ``bsbm_sharded4.resident`` at a tiny size:
its generator's two forms, its tiled reference, the cell itself on four
CPU devices, and its four per-layer readers on hand-made traces and
span records."""
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

import run as harness
from generators import bsbm, tiles
from reference import tiled
from reference.assess import Assessment, control
from repro.spans import Recorder, Span
from trace_reduce import Event, Trace

CELL = "bsbm_sharded4.resident"
TINY = {"base_triples": 4096, "tiles": 8}
PEAKS = harness.load_json(harness.HERE, "peaks.json")["devices"][
    "TPU v5 lite"]
NEW = ("scan_roofline.sharded4", "collective_share.sharded4",
       "device_idle_share.sharded4", "host_share.sharded4")
S = 1_000_000_000           # ns in a second


@pytest.fixture(scope="module")
def base():
    d = bsbm.dump(TINY["base_triples"], 2**33 + 9)
    return tiles.base(d.lines, [bsbm.NS])


def test_device_and_numpy_tiles_agree_on_every_tile(base):
    from repro.launch.mesh import make_assessment_mesh
    planes = tiles.device_tiles(base, TINY["tiles"], make_assessment_mesh(1))
    assert planes.shape == (TINY["tiles"] * base.rows, 13)
    whole = np.asarray(planes)
    for t in range(TINY["tiles"]):
        want = tiles.tile(base, t)
        assert np.array_equal(whole[t * base.rows:(t + 1) * base.rows], want)
        assert np.array_equal(tiles.device_tile(planes, base, t), want)


def test_tiles_rename_instances_only_and_keep_statements_distinct(base):
    assert np.array_equal(tiles.tile(base, 0), base.planes)
    t3 = tiles.tile(base, 3)
    changed = np.flatnonzero((t3 != base.planes).any(axis=0))
    assert changed.tolist() == [0, 2, 10, 12]    # s and o ids and hashes
    renamed_s = (base.renamed & tiles.RENAME_S) != 0
    assert np.array_equal(t3[renamed_s, 0],
                          base.planes[renamed_s, 0] + 3 * base.n_terms)
    assert renamed_s.mean() > 0.5 and (base.renamed & tiles.RENAME_O).any()
    spo = np.concatenate([tiles.tile(base, t)[:, :3]
                          for t in range(TINY["tiles"])])
    assert len(np.unique(spo, axis=0)) == TINY["tiles"] * len(
        np.unique(base.planes[:, :3], axis=0))


def test_tiled_reference_equals_the_reference_of_the_whole(base, tmp_path):
    path = str(tmp_path / "base.npz")
    base.save(path)
    whole = np.concatenate([tiles.tile(base, t)
                            for t in range(TINY["tiles"])])
    pending = tiled.Pending(path, TINY["tiles"], 12)
    try:
        folded = pending.result()
    finally:
        pending.close()
    lower = tiled.control(path, TINY["tiles"], 12)
    assert pending.seconds > 0
    for got, want in ((folded, Assessment(whole, 12)),
                      (lower, control(whole, 12))):
        assert got.n_triples == want.n_triples == len(whole)
        assert got.counts == want.counts
        assert got.values == want.values
        assert sorted(got.registers) == sorted(want.registers)
        for k, v in want.registers.items():
            assert np.array_equal(got.registers[k], v)


CELL_SCRIPT = """
import json, sys
sys.path[:0] = [{bench!r}, {src!r}]
import jax
import run as harness
harness.WORK = {work!r}

def main():
    bench, cell, config, traffic = harness.load_cell({cell!r})
    traffic = dict(traffic, **{tiny!r})
    peaks = harness.load_json(harness.HERE, 'peaks.json')['devices'][
        'TPU v5 lite']
    out = [harness.execute(bench, cell, config, traffic, 2**31 + 11, 1.0,
                           trace, jax.devices(), peaks)
           for trace in (False, True)]
    print(json.dumps(out))

if __name__ == '__main__':
    main()
"""


def test_the_cell_runs_correct_on_four_cpu_devices(tmp_path):
    script = tmp_path / "cell.py"
    script.write_text(CELL_SCRIPT.format(
        bench=harness.HERE, src=os.path.join(harness.ROOT, "src"),
        cell=CELL, tiny=TINY, work=str(tmp_path / "work")))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, str(script)], env=env,
                          cwd=str(tmp_path), capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    plain, traced = json.loads(proc.stdout.strip().splitlines()[-1])
    for out in (plain, traced):
        assert out["correct"], out["checks"]
        assert out["failed"] == 0 and out["device"]["count"] == 4
    assert set(plain["metrics"]) == {"reassess_triples_per_s", "setup_s"}
    # the CPU has no TPU planes: only the span reader reads something
    assert set(traced["metrics"]) == {"host_share.sharded4"}
    assert "# counters an assessment: transfer.bytes 0, resident.bytes " \
           f"{TINY['tiles'] * TINY['base_triples'] * 52}, scan.blocks 1" \
           in proc.stderr


def make_run(steps=(), window_s=10.0, extra=None, trace=None):
    run = harness.Run(PEAKS)
    run.steps, run.window_s = list(steps), window_s
    run.extra, run.trace = extra or {}, trace
    return run


def read(name, run):
    return harness.load_module("metrics", name).read(run)


ALL_REDUCE = "%all-reduce.3 = s32[23]{0} all-reduce(s32[23]{0} %x)"
ALL_REDUCE_START = ("%all-reduce-start.1 = s32[4096]{0} all-reduce-start("
                    "s32[4096]{0} %y)")
FUSION = "%fusion.1 = s32[4096]{0} fusion(s32[8,13]{0,1} %p), kind=kCustom"


def four_chip_trace():
    """Two chips (enough for a mean): on each, two runs of the mesh pass
    of 1 s in a 10 s window, their collectives, and a run of another
    executable with an all-reduce that does not count."""
    ops, modules = {}, {}
    for chip, coll_ms in ((0, 10), (1, 30)):
        plane = f"/device:TPU:{chip}"
        modules[plane] = [Event("jit_mesh_pass(7)", 1 * S, 2 * S),
                          Event("jit_mesh_pass(7)", 3 * S, 4 * S),
                          Event("jit_other(1)", 5 * S, 6 * S)]
        ops[plane] = [Event(FUSION, 1 * S, 1.9 * S),
                      Event(ALL_REDUCE, 1.9 * S, 1.9 * S + coll_ms * 1e6 / 2),
                      Event(ALL_REDUCE_START, 3.9 * S,
                            3.9 * S + coll_ms * 1e6 / 2),
                      Event(FUSION, 3 * S, 3.5 * S),
                      Event(ALL_REDUCE, 5.5 * S, 5.9 * S)]
    return Trace((0.0, 10.0 * S), ops, modules, [])


def test_scan_roofline_reads_rows_a_chip_over_the_mesh_pass_time():
    rows = 1 << 28
    run = make_run(extra={"rows_per_chip": rows}, trace=four_chip_trace())
    least = rows * 52 / PEAKS["hbm_bytes_per_s"]       # a run, a chip
    assert read("scan_roofline.sharded4", run) == pytest.approx(
        100 * 4 * least / 4.0)


def test_collective_share_is_the_all_reduce_time_inside_the_pass():
    run = make_run(trace=four_chip_trace())
    # chip 0: 10 ms of 2 s; chip 1: 30 ms of 2 s
    assert read("collective_share.sharded4", run) == pytest.approx(
        (0.010 / 2 + 0.030 / 2) / 2)


def test_device_idle_share_is_the_mean_over_the_chips():
    run = make_run(trace=four_chip_trace())
    # each chip: ops cover 0.9 + 0.5 + 0.4 s and the two collectives
    busy = (1.4 + 0.4 + 0.010) / 10, (1.4 + 0.4 + 0.030) / 10
    assert read("device_idle_share.sharded4", run) == pytest.approx(
        1 - sum(busy) / 2)


def recorder(spans):
    rec = Recorder()
    rec.spans.append(Span("qa.run", 0, 3 * S, -1, 1))
    for name, a, b in spans:
        rec.spans.append(Span(name, int(a * S), int(b * S), 0, 1))
    return rec


def step(rec):
    return {"triples": 1, "latency_s": 3.0,
            "answer": types.SimpleNamespace(trace=rec)}


def test_host_share_is_every_span_but_the_wait_over_the_window():
    rec = recorder([("scan.place", 0, 0.25), ("scan.dispatch", 0.25, 0.5),
                    ("scan.wait", 0.5, 2.5), ("scan.merge", 2.5, 2.75),
                    ("scan.finalize", 2.75, 3)])
    run = make_run([step(rec), step(rec)], window_s=6.0)
    assert read("host_share.sharded4", run) == pytest.approx(2 / 6)


@pytest.mark.parametrize("name", NEW)
def test_nothing_to_read_gives_none(name):
    """What the parent, without the mesh pass's name or spans, gives."""
    one_chip = Trace((0.0, S), {"/device:TPU:0": [Event(FUSION, 0, S / 2)]},
                     {"/device:TPU:0": [Event("jit_local_pass(1)", 0, S / 2)]},
                     [])
    assert read(name, make_run()) is None
    empty = {"triples": 1, "latency_s": 1.0,
             "answer": types.SimpleNamespace()}
    if name == "device_idle_share.sharded4":
        assert read(name, make_run(trace=one_chip)) == pytest.approx(0.5)
    else:
        assert read(name, make_run([empty], extra={"rows_per_chip": 8},
                                   trace=one_chip)) is None


def test_the_cell_is_declared_with_its_metrics():
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert cell["chips"] == 4 and cell["traffic"] == "resident"
    assert {m["name"] for m in harness.cell_metrics(bench, CELL, False)} == {
        "reassess_triples_per_s", "setup_s"}
    assert {m["name"] for m in harness.cell_metrics(bench, CELL, True)} == \
        set(NEW)
    _, _, config, traffic = harness.load_cell(CELL)
    rows = traffic["tiles"] * traffic["base_triples"]
    assert rows == 805_306_368 and rows % (config["chips"] << 21) == 0
