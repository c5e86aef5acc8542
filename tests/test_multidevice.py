"""Multi-device behaviours (8 fake CPU devices via subprocess — the main
test process keeps seeing 1 device)."""
import pytest

from conftest import run_subprocess_devices


@pytest.mark.slow
def test_distributed_evaluator_matches_single():
    out = run_subprocess_devices(8, """
import json
import numpy as np
from repro.rdf import synth_encoded
from repro.core import QualityEvaluator, ALL_METRICS
from repro.launch.mesh import make_host_mesh
tt = synth_encoded(20000, seed=11)
single = QualityEvaluator(ALL_METRICS, backend='jnp').assess(tt)
mesh = make_host_mesh(model=2)
dist = QualityEvaluator(ALL_METRICS, backend='pallas', mesh=mesh).assess(tt)
err = max(abs(single.values[k] - dist.values[k]) for k in single.values)
print(json.dumps({'err': float(err)}))
""")
    assert out["err"] < 1e-6


@pytest.mark.slow
def test_fused_scan_mesh_bit_identical_and_uneven_shards():
    """The fused_scan megakernel under shard_map: values AND HLL register
    banks must equal the 1-device run bit-for-bit, including a row count
    not divisible by the device count (uneven final shard — padding rows
    carry zero flag planes, invisible to counters and sketches)."""
    out = run_subprocess_devices(8, """
import json
import numpy as np
import jax
from repro.rdf import synth_encoded
from repro.core import QualityEvaluator, ALL_METRICS
res = {}
for n in (20000, 20003):        # 20003 % 8 != 0: uneven shards
    tt = synth_encoded(n, seed=11)
    single = QualityEvaluator(ALL_METRICS, backend='fused_scan').assess(tt)
    mesh = jax.make_mesh((8,), ('data',))
    dist = QualityEvaluator(ALL_METRICS, backend='fused_scan',
                            mesh=mesh).assess(tt)
    res[str(n)] = {
        'values': bool(single.values == dist.values),
        'regs': bool(all(np.array_equal(single.registers[k],
                                        dist.registers[k])
                         for k in single.registers)),
        'passes': dist.passes,
    }
print(json.dumps(res))
""")
    for n, r in out.items():
        assert r["values"], f"n={n}: values differ"
        assert r["regs"], f"n={n}: registers differ"
        assert r["passes"] == 1, f"n={n}: fused_scan is a 1-pass kernel"


@pytest.mark.slow
def test_chunked_prefetch_mesh_bit_identical():
    """Chunked + async-prefetch execution over a mesh: every chunk's rows
    shard across devices, and the merged result (values + registers) must
    equal the single-device single-shot run exactly."""
    out = run_subprocess_devices(8, """
import json
import numpy as np
import jax
from repro import qa
from repro.core import QualityEvaluator, ALL_METRICS
from repro.rdf import synth_encoded
tt = synth_encoded(30000, seed=7)
single = QualityEvaluator(ALL_METRICS, backend='jnp').assess(tt)
mesh = jax.make_mesh((8,), ('data',))
res = (qa.pipeline().metrics(ALL_METRICS).backend('fused_scan')
       .shard(mesh).chunked(6).pipelined(2).run(tt))
print(json.dumps({
    'values': bool(single.values == res.values),
    'regs': bool(all(np.array_equal(single.registers[k], res.registers[k])
                     for k in single.registers)),
    'devices': res.exec_stats.devices,
    'mode': res.exec_stats.mode,
}))
""")
    assert out["values"] and out["regs"]
    assert out["devices"] == 8
    assert out["mode"] == "pipelined"


@pytest.mark.slow
def test_incremental_store_mesh_rescan_bit_identical():
    """Incremental store rescans across the mesh (whole segments batched
    one-per-device): cold and warm-after-mutation runs must stay bit-
    identical to cold single-device assessments, with edit-local reuse."""
    out = run_subprocess_devices(8, """
import json, tempfile
import numpy as np
import jax
from repro import qa
from repro.core import ALL_METRICS
from repro.rdf import bsbm_ntriples

BASE = ('http://bsbm.example.org/',)
SEG = 16384
data = bsbm_ntriples(300, seed=11).encode()

def pipe(mesh=None, store=None):
    p = qa.pipeline().metrics(ALL_METRICS).backend('fused_scan').base(*BASE)
    if mesh is not None:
        p = p.shard(mesh)
    if store is not None:
        p = p.incremental(store, segment_bytes=SEG)
    return p

def same(a, b):
    return bool(a.values == b.values and a.n_triples == b.n_triples
                and all(np.array_equal(a.registers[k], b.registers[k])
                        for k in b.registers))

mesh = jax.make_mesh((8,), ('data',))
store = tempfile.mkdtemp()
cold = pipe().run(data.decode())
inc1 = pipe(mesh=mesh, store=store).run(data.decode())

mid = data.find(b'\\n', len(data) // 2) + 1
end = data.find(b'\\n', mid) + 1
mutated = (data[:mid] + b'<http://x/s> <http://x/p> <http://x/o> .\\n'
           + data[end:])
cold_mut = pipe().run(mutated.decode())
inc2 = pipe(mesh=mesh, store=store).run(mutated.decode())
s1, s2 = inc1.exec_stats, inc2.exec_stats
print(json.dumps({
    'cold_ok': same(inc1, cold), 'mut_ok': same(inc2, cold_mut),
    'mode': s1.mode, 'devices': s1.devices,
    'rescanned_warm': s2.segments_rescanned,
    'reused_warm': s2.segments_reused,
    'passes_warm': inc2.passes,
}))
""")
    assert out["cold_ok"] and out["mut_ok"]
    assert out["mode"] == "incremental+mesh"
    assert out["devices"] == 8
    assert out["rescanned_warm"] <= 2          # edit-local reuse held
    assert out["reused_warm"] >= 1
    assert out["passes_warm"] == out["rescanned_warm"]  # measured passes


@pytest.mark.slow
def test_mesh_pass_accounting_measured():
    """passes_per_chunk under a mesh traces the MAPPED pass functions —
    the counter must report the same per-chunk pass count as the local
    path (SPMD: one logical pass over the data regardless of shards)."""
    out = run_subprocess_devices(8, """
import json
import jax
from repro.core import QualityEvaluator, ALL_METRICS
mesh = jax.make_mesh((8,), ('data',))
local = QualityEvaluator(ALL_METRICS, backend='fused_scan')
dist = QualityEvaluator(ALL_METRICS, backend='fused_scan', mesh=mesh)
jnp_dist = QualityEvaluator(ALL_METRICS, backend='jnp', mesh=mesh)
print(json.dumps({'local': local.passes_per_chunk,
                  'dist': dist.passes_per_chunk,
                  'jnp_dist': jnp_dist.passes_per_chunk}))
""")
    assert out["dist"] == out["local"] == 1
    assert out["jnp_dist"] >= 1


@pytest.mark.slow
def test_eval_segment_batch_matches_per_segment():
    """The batched per-segment mesh executor returns, for every segment
    in the batch, exactly what eval_chunk returns for that segment alone
    — including a batch size not divisible by the device count."""
    out = run_subprocess_devices(8, """
import json
import numpy as np
import jax
from repro.core import QualityEvaluator, ALL_METRICS
from repro.rdf import synth_encoded
mesh = jax.make_mesh((8,), ('data',))
ev = QualityEvaluator(ALL_METRICS, backend='fused_scan', mesh=mesh)
ref = QualityEvaluator(ALL_METRICS, backend='fused_scan')
tensors = [synth_encoded(n, seed=s)
           for s, n in enumerate((1000, 3000, 500, 2000, 700))]  # 5 % 8
outs = ev.eval_segment_batch(tensors)
ok = True
for tt, (counts, regs) in zip(tensors, outs):
    c_ref, r_ref = ref.eval_chunk(tt)
    ok = ok and all(np.array_equal(a, np.asarray(b, np.int64))
                    for a, b in zip(counts, c_ref))
    ok = ok and all(np.array_equal(regs[k], r_ref[k]) for k in r_ref)
print(json.dumps({'ok': bool(ok), 'n': len(outs)}))
""")
    assert out["ok"] and out["n"] == 5


@pytest.mark.slow
def test_elastic_checkpoint_restore_across_meshes():
    """State written under a (4,2) mesh restores onto a (2,4) mesh."""
    out = run_subprocess_devices(8, """
import json, tempfile
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint import CheckpointManager
d = tempfile.mkdtemp()
mesh_a = jax.make_mesh((4, 2), ('data', 'model'))
tree = {'w': jax.device_put(np.arange(64.0).reshape(8, 8),
                            NamedSharding(mesh_a, P('data', 'model')))}
mgr = CheckpointManager(d)
mgr.save(1, tree)
mesh_b = jax.make_mesh((2, 4), ('data', 'model'))
shard_b = {'w': NamedSharding(mesh_b, P('data', 'model'))}
out = mgr.restore(1, {'w': np.zeros((8, 8))}, shardings=shard_b)
ok = bool((np.asarray(out['w']) == np.arange(64.0).reshape(8, 8)).all())
print(json.dumps({'ok': ok}))
""")
    assert out["ok"]
