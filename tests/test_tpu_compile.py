"""Ahead-of-time compiles of the main path's kernels for a TPU v5e chip
that is described, not attached: the TPU compiler refuses here what the
chip would refuse (a block over the scoped VMEM, an unaligned slice, a
program over the device's memory), at no chip time.  Real widths: the
``all`` plan over (2^20, 13) int32 planes at the block sizes the VMEM model
chooses, and the row-sharded ``fused_scan`` pass over a 2x2 mesh.

The topology is described in a fixture, never at import: only one process
may load the TPU library, and every test worker imports this file.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import QualityEvaluator
from repro.core import sketches as hll
from repro.core.metrics import ALL_METRICS, get_metrics
from repro.core.planner import plan
from repro.kernels import SCOPED_VMEM_BYTES
from repro.kernels.fused_scan import kernel as fkernel, ops as fops
from repro.kernels.hll import ops as hops
from repro.kernels.qap_count import kernel as qkernel, ops as qops
from repro.rdf.triple_tensor import N_PLANES

ROWS = 1 << 20
ALL_PLAN = plan(get_metrics(ALL_METRICS))
SKETCH_COLS = tuple(cols for _, cols in ALL_PLAN.sketch_specs)
HBM_BYTES = 16 * 10**9                      # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache off meanwhile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            described = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure skips
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield described
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), "kernel not lowered"
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes) < HBM_BYTES
    return compiled


@pytest.mark.parametrize("p", [12, 14])
def test_fused_scan_compiles_at_model_block(one_chip, p):
    block = fops.default_block_n(ALL_PLAN.program, SKETCH_COLS, p)
    assert fkernel.vmem_bytes(ALL_PLAN.program, SKETCH_COLS, p,
                              block) <= SCOPED_VMEM_BYTES
    x = jax.ShapeDtypeStruct((ROWS, N_PLANES), jnp.int32, sharding=one_chip)
    _compile(lambda a: fops.fused_scan(
        a, ALL_PLAN.program, ALL_PLAN.n_counters, ALL_PLAN.sketch_specs, p,
        interpret=False), x)


def test_qap_count_compiles_at_model_block(one_chip):
    block = qops.default_block_n(ALL_PLAN.program)
    assert qkernel.vmem_bytes(ALL_PLAN.program, block) <= SCOPED_VMEM_BYTES
    x = jax.ShapeDtypeStruct((ROWS, N_PLANES), jnp.int32, sharding=one_chip)
    _compile(lambda a: qops.fused_count(
        a, ALL_PLAN.program, ALL_PLAN.n_counters, interpret=False), x)


@pytest.mark.parametrize("p", [12, 14])
def test_hll_fold_compiles(one_chip, p):
    x = jax.ShapeDtypeStruct((ROWS, N_PLANES), jnp.int32, sharding=one_chip)
    _compile(lambda a: hops.hll_fold(a, SKETCH_COLS[0], p,
                                     interpret=False), x)


def test_row_sharded_fused_scan_compiles_on_2x2(topo, monkeypatch):
    """The evaluator's mesh pass — per-device kernel grid, psum/pmax —
    over the four chips of a v5e:2x2 host, at the four-chip smoke's
    2^25 + 3 triples padded to a device multiple.  ``jax.default_backend``
    is the CPU here, so the test steers the platform's interpret choice."""
    monkeypatch.setattr(fops, "interpret_mode", lambda: False)
    mesh = Mesh(np.array(topo.devices[:4]), ("data",))
    ev = QualityEvaluator(ALL_METRICS, backend="fused_scan", mesh=mesh)
    rows = -(-((1 << 25) + 3) // ev._row_multiple()) * ev._row_multiple()
    x = jax.ShapeDtypeStruct((rows, N_PLANES), jnp.int32,
                             sharding=NamedSharding(mesh, P("data")))
    compiled = ev._pass_fns[0].lower(x).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-reduce" in text              # the psum/pmax merge
    mem = compiled.memory_analysis()         # per device
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes) < HBM_BYTES


def test_jnp_pass_folds_sketches_without_a_scatter(one_chip, monkeypatch):
    """The ``jnp`` ``all`` pass over (2^20, 13) planes on one chip folds
    both HLL sketches by the count form: no scatter in its program, and
    the one-hots fused into the dots, so the temporaries stay at 1.16 MB
    as with the scatter.  ``jax.default_backend`` is the CPU here, so the
    test steers the platform's one-hot choice."""
    monkeypatch.setattr(hll, "fuses_one_hots", lambda: True)
    ev = QualityEvaluator(ALL_METRICS, backend="jnp")
    x = jax.ShapeDtypeStruct((ROWS, N_PLANES), jnp.int32, sharding=one_chip)
    compiled = jax.jit(ev._local_pass_fn(ev.plans[0])).lower(x).compile()
    assert " scatter(" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * 2**20


def test_row_blocked_jnp_pass_holds_bsbm_200gb_on_2x2(topo, monkeypatch):
    """The ``jnp`` mesh pass over 3 x 2^28 rows, the resident four-chip
    BSBM cell's dataset: 12.88 GB of planes a chip.  The pass walks each
    shard in row blocks, so its temporaries stay those of one block and
    argument plus temporaries fit the 15.75 GB a program may use; a pass
    over the whole shard at once needs 17.44 GB.  The sketches' one-hots
    fuse into their dots as on the chip."""
    monkeypatch.setattr(hll, "fuses_one_hots", lambda: True)
    mesh = Mesh(np.array(topo.devices[:4]), ("data",))
    ev = QualityEvaluator(ALL_METRICS, backend="jnp", mesh=mesh)
    x = jax.ShapeDtypeStruct((3 << 28, N_PLANES), jnp.int32,
                             sharding=NamedSharding(mesh, P("data")))
    compiled = ev._pass_fns[0].lower(x).compile()
    text = compiled.as_text()
    assert "all-reduce" in text and "while" in text
    mem = compiled.memory_analysis()         # per device
    assert mem.temp_size_in_bytes < 100 * 10**6
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9
