"""Resident planes and the row-blocked pass: a ``TripleTensor`` whose
planes are already on the devices is scanned where it lies, and a device
walks rows past ``SCAN_BLOCK_ROWS`` in blocks, with results equal bit for
bit to a single-device run of the same planes from the host."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_subprocess_devices
from repro import spans
from repro.core import ALL_METRICS, QualityEvaluator, evaluator
from repro.kernels import count_scans
from repro.rdf import TripleTensor, synth_encoded

# rows a device holds past a monkeypatched block of 1,024 (4 devices):
# three blocks and a half; then a count that is a multiple of neither the
# block nor the device count (padded on the device, an uneven tail)
MESH_ROWS = [4 * (3 * 1024 + 512), 10_003]


@pytest.mark.parametrize("n", MESH_ROWS)
def test_resident_mesh_run_equals_host_planes_bit_for_bit(n):
    out = run_subprocess_devices(4, f"""
import json
import numpy as np
import jax
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import qa
from repro.core import evaluator
from repro.launch.mesh import make_assessment_mesh
from repro.rdf import TripleTensor, synth_encoded
evaluator.SCAN_BLOCK_ROWS = 1024
n = {n}
tt = synth_encoded(n, seed=5)
host = qa.pipeline().metrics('all').run(tt)
mesh = make_assessment_mesh(4)
rows = P('data') if n % 4 == 0 else P()
planes = jax.device_put(tt.planes, NamedSharding(mesh, rows))
pipe = qa.pipeline().metrics('all').shard(mesh)
with jax.transfer_guard_host_to_device('disallow'):
    res = pipe.run(TripleTensor(planes, tt.n_valid, tt.n_terms))
placed = pipe.evaluator()._placed(planes)
print(json.dumps({{
    'values': res.values == host.values,
    'counts': res.counts == host.counts,
    'n': res.n_triples == host.n_triples,
    'regs': sorted(res.registers) == sorted(host.registers) and all(
        np.array_equal(res.registers[k], host.registers[k])
        for k in host.registers),
    'passes': [res.passes, host.passes],
    'placed': placed,
    'counters': res.trace.counts,
    'spans': sorted({{s.name for s in res.trace.spans}}),
}}))
""")
    assert out["values"] and out["counts"] and out["n"] and out["regs"]
    assert out["passes"][0] == out["passes"][1]
    # a multiple of the device count lies as the pass reads it; the other
    # is padded and resharded on the devices, inside ``scan.place``
    assert out["placed"] == (n % 4 == 0)
    rows = -(-n // 4) * 4
    assert out["counters"]["resident.bytes"] == rows * 13 * 4
    assert out["counters"]["scan.blocks"] == -(-(rows // 4) // 1024)
    assert out["counters"]["scan.sketch_count_rows"] == 2 * rows
    assert "transfer.bytes" not in out["counters"]
    assert "scan.place" in out["spans"]
    assert "scan.transfer" not in out["spans"]


def _pass_text(monkeypatch, rows: int, block: int) -> str:
    monkeypatch.setattr(evaluator, "SCAN_BLOCK_ROWS", block)
    ev = QualityEvaluator(ALL_METRICS, backend="jnp")
    shape = jax.ShapeDtypeStruct((rows, 13), jnp.int32)
    return ev._pass_fns[0].lower(shape).as_text()


@pytest.mark.parametrize("rows", [8, 512, 1024])
def test_a_pass_over_at_most_one_block_lowers_without_a_loop(monkeypatch,
                                                             rows):
    text = _pass_text(monkeypatch, rows, 1024)
    assert "stablehlo.while" not in text
    assert text.startswith("module @jit_local_pass")


@pytest.mark.parametrize("rows", [1032, 4096])
def test_a_pass_over_more_rows_walks_them_in_a_loop(monkeypatch, rows):
    assert "stablehlo.while" in _pass_text(monkeypatch, rows, 1024)


def test_the_blocked_pass_counts_one_pass_per_plan(monkeypatch):
    monkeypatch.setattr(evaluator, "SCAN_BLOCK_ROWS", 64)
    ev = QualityEvaluator(ALL_METRICS, backend="jnp")
    fn = ev._local_pass_fn(ev.plans[0])
    with count_scans() as box:
        jax.eval_shape(fn, jax.ShapeDtypeStruct((64 * 3 + 8, 13), jnp.int32))
    assert box[0] == ev.passes_per_chunk == 1 + len(ev.plans[0].sketch_specs)


def test_the_blocked_pass_equals_one_block(monkeypatch):
    tt = synth_encoded(1000, seed=3)
    whole = QualityEvaluator(ALL_METRICS).assess(tt)
    monkeypatch.setattr(evaluator, "SCAN_BLOCK_ROWS", 96)
    blocked = QualityEvaluator(ALL_METRICS).assess(tt)
    assert blocked.values == whole.values and blocked.counts == whole.counts
    for k, v in whole.registers.items():
        np.testing.assert_array_equal(blocked.registers[k], v)


def test_scan_blocks_counts_blocks_per_device():
    block = evaluator.SCAN_BLOCK_ROWS
    assert [evaluator.scan_blocks(r) for r in
            (1, block, block + 1, 96 * block)] == [1, 1, 2, 96]


def test_device_planes_are_padded_on_the_device():
    tt = synth_encoded(1001, seed=2)
    arr = jax.device_put(tt.planes)
    with jax.transfer_guard_host_to_device("disallow"):
        padded = TripleTensor(arr, tt.n_valid, tt.n_terms).padded_to(8)
    assert isinstance(padded.planes, jax.Array)
    assert padded.planes.shape == (1008, 13) and padded.n_valid == 1001
    got = np.asarray(padded.planes)
    np.testing.assert_array_equal(got[:1001], tt.planes)
    assert not got[1001:].any()


def test_single_device_resident_run_records_no_transfer():
    tt = synth_encoded(2000, seed=4)
    ev = QualityEvaluator(["L1", "CN2_EXACT"])
    host = ev.assess(tt)
    with spans.run() as rec, jax.transfer_guard_host_to_device("disallow"):
        res = ev.assess(TripleTensor(jax.device_put(tt.planes), tt.n_valid,
                                     tt.n_terms))
    assert res.values == host.values
    assert rec.counts == {"resident.bytes": tt.planes.nbytes,
                          "scan.blocks": 1,
                          "scan.sketch_count_rows": tt.planes.shape[0]}
    assert "scan.place" in {s.name for s in rec.spans}
