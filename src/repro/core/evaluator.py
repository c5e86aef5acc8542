"""Distributed QAP evaluator (paper §2.2 step 4 + Algorithm 1).

Execution modes:

* ``fused=True`` (ours, beyond-paper): ONE plan over the main dataset
  evaluates every requested metric — the planner's deduped bytecode.
* ``fused=False`` (paper-faithful Algorithm 1): ``foreach m ∈ metrics`` run a
  separate pass; this is the §Perf baseline.
* ``backend='jnp' | 'pallas' | 'fused_scan'``: mask-based XLA path, the
  two-kernel Pallas path (``kernels/qap_count`` + one ``kernels/hll`` scan
  per sketch — ``1 + S`` data passes), or the one-true-pass megakernel
  (``kernels/fused_scan``: counters AND every sketch register bank per
  VMEM-resident block — exactly 1 data pass).
* ``mesh``: when given, rows are sharded over *all* mesh axes (quality
  assessment is purely data-parallel — every chip is a Spark "worker") and
  counters/sketches are reduced with ``psum``/``pmax`` inside ``shard_map``.
  Every backend distributes, the ``fused_scan`` megakernel included: the
  local pass runs a per-device Pallas grid over that device's row shard,
  then counter vectors ``psum`` and register banks ``pmax`` across every
  axis.  ``device_planes`` pads rows up to a device multiple first —
  padding rows carry zero flag planes, so an uneven final shard is
  invisible to counters and sketches alike.  A device walks more rows
  than ``SCAN_BLOCK_ROWS`` in blocks of that many (``lax.fori_loop``
  carrying the counters and register banks), so a pass's temporaries are
  those of one block however much a chip holds; the collectives run once,
  after the loop.  A ``TripleTensor`` whose planes are already a
  ``jax.Array`` placed with the pass's row sharding (a resident dataset)
  is scanned where it lies.  ``eval_segment_batch``
  additionally distributes *whole segments* (one independent dataset
  slice per device slot — the embarrassingly-parallel axis incremental
  rescans use, where per-segment results must come back unreduced).

``AssessmentResult.passes`` reports ACTUAL data passes: each op wrapper
that streams the planes once records a scan (``kernels.record_scan``), and
``passes_per_chunk`` traces the pass functions under that counter.  Under
a mesh the *mesh-mapped* function is traced — the SPMD program every
device runs — so the count reflects what actually executes (a replicated
or side-scanning mesh path would show up), not just the single-device
body it was built from.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import spans
from ..kernels import count_scans, record_scan
from ..rdf.triple_tensor import TripleTensor, COL_S_FLAGS, N_PLANES
from . import sketches as hll
from .expr import eval_program_jnp
from .metrics import ALL_METRICS, Metric, get_metrics
from .planner import Plan, plan, plan_single

BACKENDS = ("jnp", "pallas", "fused_scan")

# Rows one device scans at a time.  A pass over more rows walks them in
# blocks of this many, so that its temporaries stay those of one block
# (under a MB on a TPU v5e) however much a chip holds; over a whole shard
# at once the HLL scatter's bucket and rank arrays take ~29 B a row.  A
# shard of at most one block is scanned without a loop.
SCAN_BLOCK_ROWS = 1 << 21


def scan_blocks(rows: int) -> int:
    """Row blocks a pass walks over one device's ``rows``."""
    return max(1, -(-rows // SCAN_BLOCK_ROWS))


_hll_estimate = jax.jit(hll.hll_estimate)


def _merge_pass(acc, out):
    """Counters summed, register banks max-merged: one block's result
    folded into the running one."""
    return (acc[0] + out[0],
            {k: jnp.maximum(acc[1][k], out[1][k]) for k in acc[1]})


def _row_blocked(block_pass):
    """``block_pass`` (rows -> counters, registers) over a device's rows
    in ``SCAN_BLOCK_ROWS`` blocks; a tail shorter than a block is one step
    more.  At most one block is ``block_pass`` itself, with no loop.

    ``block_pass`` is traced for the loop's body, once more for a tail,
    and once for the shapes of the running result; its ``record_scan``
    calls count the body alone, since together they read the rows once."""

    def local_pass(planes):
        block = SCAN_BLOCK_ROWS
        if planes.shape[0] <= block:
            return block_pass(planes)
        n_full, tail = divmod(planes.shape[0], block)
        with count_scans():
            shapes = jax.eval_shape(block_pass, jax.ShapeDtypeStruct(
                (block, planes.shape[1]), planes.dtype))
        zeros = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)

        def body(i, acc):
            rows = jax.lax.dynamic_slice_in_dim(planes, i * block, block)
            return _merge_pass(acc, block_pass(rows))

        acc = jax.lax.fori_loop(0, n_full, body, zeros)
        if tail:
            with count_scans():
                acc = _merge_pass(acc, block_pass(planes[n_full * block:]))
        return acc

    return local_pass


@dataclasses.dataclass
class AssessmentResult:
    values: dict[str, float]            # metric name -> value
    counts: dict[str, dict[str, int]]   # metric -> counter -> raw count
    sketch_estimates: dict[str, float]
    n_triples: int
    passes: int                         # ACTUAL data passes performed
    exec_stats: object = None           # dist.ChunkStats when run chunked
    trace: object = None                # spans.Recorder of the run
    # merged HLL register banks (sketch name -> int32 array); exposed so
    # exactness can be asserted at the register level, not just on the
    # derived estimates
    registers: dict = dataclasses.field(default_factory=dict)

    def __getitem__(self, k: str) -> float:
        return self.values[k]


def _counts_jnp(planes, program, n_counters):
    return eval_program_jnp(planes, program, n_counters)


def _counts_masks(planes, exprs):
    """Direct AST evaluation — an independent path from the bytecode
    interpreter, used to cross-check both in tests."""
    from .expr import VALID_BIT, VALID_PLANE
    valid = (planes[:, VALID_PLANE] & VALID_BIT) != 0
    return jnp.stack([jnp.sum(e.to_mask(planes) & valid, dtype=jnp.int32)
                      for e in exprs])


class QualityEvaluator:
    def __init__(self, metric_names: Sequence[str] = ALL_METRICS, *,
                 fused: bool = True, backend: str = "jnp",
                 mesh: Mesh | None = None, hll_p: int = hll.DEFAULT_P):
        if backend not in BACKENDS:
            raise ValueError(
                f"backend must be one of {BACKENDS}, got {backend!r}")
        self.metrics = get_metrics(metric_names)
        self.fused = fused
        self.backend = backend
        self.mesh = mesh
        self.hll_p = hll_p
        self.plans: list[Plan] = (
            [plan(self.metrics)] if fused
            else [plan_single(m) for m in self.metrics])

    # -- single-pass core (one plan) ------------------------------------------
    def _local_pass_fn(self, pln: Plan):
        """The un-jitted single-device pass planes -> (counts, sketches),
        walking the rows in ``SCAN_BLOCK_ROWS`` blocks.

        Each branch declares its HBM data passes via ``record_scan`` (op
        wrappers do it for the kernel paths), so tracing this function under
        ``kernels.count_scans`` measures passes-per-execution — the hook
        behind ``passes_per_chunk``.
        """
        program, n_counters = pln.program, pln.n_counters
        sketch_specs = pln.sketch_specs
        backend, hll_p = self.backend, self.hll_p

        def block_pass(planes):
            if backend == "fused_scan":
                from ..kernels.fused_scan import ops as fops
                counts, regs = fops.fused_scan(
                    planes, program, n_counters, sketch_specs, hll_p)
                return counts, regs
            if backend == "pallas":
                from ..kernels.qap_count import ops as qops
                counts = qops.fused_count(planes, program, n_counters)
            else:
                record_scan(1)  # the counts scan
                counts = _counts_jnp(planes, program, n_counters)
            regs = {}
            if sketch_specs:
                valid = planes[:, COL_S_FLAGS] != 0  # any flag bit ⇒ real row
                for sname, cols in sketch_specs:
                    if backend == "pallas":
                        from ..kernels.hll import ops as hops
                        regs[sname] = hops.hll_fold(planes, cols, hll_p)
                    else:
                        record_scan(1)  # one more scan per sketch
                        regs[sname] = hll.hll_update(
                            hll.hll_init(hll_p), planes, cols, valid=valid)
            return counts, regs

        return _row_blocked(block_pass)

    def _pass_fn(self, pln: Plan):
        """Build the jitted (and mesh-mapped) pass function for one plan."""
        local_pass = self._local_pass_fn(pln)
        if self.mesh is None:
            return jax.jit(local_pass)

        mesh = self.mesh
        axes = tuple(mesh.axis_names)

        # its executable is ``jit_mesh_pass``: the name the device trace
        # finds the row-sharded pass by
        def mesh_pass(planes):
            counts, regs = local_pass(planes)
            for ax in axes:
                counts = jax.lax.psum(counts, ax)
                regs = {k: jax.lax.pmax(v, ax) for k, v in regs.items()}
            return counts, regs

        shard_rows = P(axes)  # rows split over every axis (pure DP)
        mapped = jax.shard_map(
            mesh_pass, mesh=mesh,
            in_specs=(shard_rows,),
            out_specs=(P(), {s: P() for s, _ in pln.sketch_specs}),
            check_vma=False,  # pallas_call outputs carry no vma info
        )
        return jax.jit(mapped)

    @functools.cached_property
    def _pass_fns(self):
        return [self._pass_fn(p) for p in self.plans]

    @functools.cached_property
    def _count_form_sketches(self) -> int:
        """Sketches a pass over every plan folds by the count form: the
        ``jnp`` backend's, at a p that ``hll.hll_update`` folds so.  Times
        the rows a pass reads, it is the counter
        ``scan.sketch_count_rows``, recorded where it is not 0."""
        if self.backend != "jnp" or not hll.uses_count_form(self.hll_p):
            return 0
        return sum(len(pln.sketch_specs) for pln in self.plans)

    @functools.cached_property
    def passes_per_chunk(self) -> int:
        """ACTUAL HBM data passes one chunk evaluation performs, measured
        by tracing every plan's pass function under the scan counter — 1
        per plan for jnp/fused_scan-style fused scans, ``1 + S`` for the
        two-kernel pallas path with S sketches.

        Mesh-aware: with a mesh, the traced function is the *mesh-mapped*
        one (``shard_map`` body + cross-axis reductions) — the SPMD
        program each device executes over its row shard.  One recorded
        scan there means every device streams its shard once, i.e. the
        sharded dataset streams HBM→VMEM once collectively; if the mesh
        path ever replicated work or added a side-scan, this measurement
        (unlike tracing only the single-device body) would report it.
        Fresh (un-jit-cached) functions are traced on purpose: a jit
        cache hit would skip tracing and silently count zero.
        """
        shape = jax.ShapeDtypeStruct((max(8, self._row_multiple()), N_PLANES),
                                     jnp.int32)
        with count_scans() as box:
            for pln in self.plans:
                fn = (self._local_pass_fn(pln) if self.mesh is None
                      else self._pass_fn(pln))
                jax.eval_shape(fn, shape)
        return box[0]

    def _shard_count(self) -> int:
        """Row shards a mesh splits a chunk into (1 without a mesh)."""
        if self.mesh is None:
            return 1
        return int(np.prod(self.mesh.devices.shape))

    def _row_multiple(self) -> int:
        per_device = 8 if self.backend in ("pallas", "fused_scan") else 1
        return self._shard_count() * per_device

    def _sharding(self):
        """Leading axis split over every mesh axis; None (the default
        device) without a mesh."""
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, P(tuple(self.mesh.axis_names)))

    def _placed(self, arr: jax.Array) -> bool:
        """Whether ``arr`` lies where the pass reads it: a row count the
        pass can split, under the pass's row sharding (on one device
        without a mesh)."""
        if arr.shape[0] % max(1, self._row_multiple()):
            return False
        if self.mesh is None:
            return len(arr.sharding.device_set) == 1
        return arr.sharding.is_equivalent_to(self._sharding(), arr.ndim)

    def device_planes(self, tensor: TripleTensor):
        if isinstance(tensor.planes, jax.Array):
            return self._resident_planes(tensor)
        # host NumPy goes straight to its shards: no staging copy of the
        # whole chunk on one device.  The span ends when the copy has
        # landed, so it times the transfer and not its enqueue.
        with spans.span("scan.transfer"):
            padded = tensor.padded_to(max(1, self._row_multiple()))
            arr = jax.device_put(padded.planes, self._sharding())
            arr.block_until_ready()
        spans.count("transfer.bytes", padded.planes.nbytes)
        return arr

    def _resident_planes(self, tensor: TripleTensor):
        """Planes already on the device, scanned where they lie: padded
        on the device and resharded device to device only where they do
        not lie as the pass reads them.  Nothing crosses from the host."""
        with spans.span("scan.place"):
            arr = tensor.planes
            if not self._placed(arr):
                arr = tensor.padded_to(max(1, self._row_multiple())).planes
                arr = jax.device_put(
                    arr, self._sharding() or jax.local_devices()[0])
                arr.block_until_ready()
        spans.count("resident.bytes", arr.nbytes)
        return arr

    # -- public API ------------------------------------------------------------
    def assess(self, tensor: TripleTensor) -> AssessmentResult:
        """Single-shot assessment.

        Backward-compat shim over the shared execution path the
        ``repro.qa`` pipeline uses. Prefer ``repro.qa.pipeline()`` /
        ``repro.qa.assess`` for new code (they add ingest, chunked
        execution, and checkpoint/resume).
        """
        return run_single_shot(self, tensor)

    # -- mergeable chunk interface (fault tolerance / stragglers) -------------
    def _all_sketch_specs(self) -> tuple:
        specs: dict[str, tuple[int, ...]] = {}
        for pln in self.plans:
            for s, cols in pln.sketch_specs:
                if specs.get(s, cols) != cols:
                    raise ValueError(
                        f"sketch {s!r} defined with conflicting columns "
                        f"{specs[s]} vs {cols}")
                specs[s] = cols
        return tuple(specs.items())

    def chunk_state_init(self) -> dict:
        """Empty mergeable state: one counter vector per plan + sketches."""
        return {
            "counts": [np.zeros((pln.n_counters,), np.int64)
                       for pln in self.plans],
            "sketches": {s: np.zeros((1 << self.hll_p,), np.int32)
                         for s, _ in self._all_sketch_specs()},
            "chunks_done": set(),
        }

    def dispatch_chunk(self, arr):
        """Launch every plan's pass over device-resident ``arr`` WITHOUT
        blocking (JAX dispatch is async) — the device-side half of
        ``eval_chunk``.  Pair with ``materialize_chunk``."""
        spans.count("scan.blocks", len(self._pass_fns) * scan_blocks(
            arr.shape[0] // self._shard_count()))
        if self._count_form_sketches:
            spans.count("scan.sketch_count_rows",
                        self._count_form_sketches * arr.shape[0])
        with spans.span("scan.dispatch"):
            return [fn(arr) for fn in self._pass_fns]

    @staticmethod
    def materialize_chunk(outs):
        """Block until the dispatched passes finish and gather host numpy
        results — the single per-chunk host synchronization point.
        Callers time it as ``scan.wait``."""
        counts_out, regs_out = [], {}
        for counts, regs in outs:
            counts_out.append(np.asarray(counts, np.int64))
            regs_out.update({k: np.asarray(v) for k, v in regs.items()})
        return counts_out, regs_out

    def eval_chunk(self, chunk: TripleTensor):
        arr = self.device_planes(chunk)
        outs = self.dispatch_chunk(arr)
        with spans.span("scan.wait"):
            return self.materialize_chunk(outs)

    # -- batched independent segments (mesh scale-out of incremental runs) -----
    def _batch_pass_fn(self, pln: Plan):
        """One plan's pass over a ``(B, R, N_PLANES)`` stack of independent
        row blocks → per-block ``((B, n_counters), {sketch: (B, 2^p)})``.

        Under a mesh the BATCH dimension is sharded (one whole block per
        device slot, ``P(axes)`` in and out) and nothing is cross-device
        reduced — unlike ``_pass_fn``, which shards the rows of ONE block
        and ``psum``/``pmax``-merges.  This is the execution shape of the
        paper's Spark stage before the ``reduce``: independent partitions
        assessed in parallel, partial states kept separate (the segment
        store must freeze each one).
        """
        local_pass = self._local_pass_fn(pln)

        def batch_pass(planes):                 # (b, R, P) local blocks
            outs = [local_pass(planes[i]) for i in range(planes.shape[0])]
            counts = jnp.stack([c for c, _ in outs])
            regs = {k: jnp.stack([r[k] for _, r in outs])
                    for k in outs[0][1]}
            return counts, regs

        if self.mesh is None:
            return jax.jit(batch_pass)
        shard_batch = P(tuple(self.mesh.axis_names))
        mapped = jax.shard_map(
            batch_pass, mesh=self.mesh,
            in_specs=(shard_batch,),
            out_specs=(shard_batch,
                       {s: shard_batch for s, _ in pln.sketch_specs}),
            check_vma=False,
        )
        return jax.jit(mapped)

    @functools.cached_property
    def _batch_pass_fns(self):
        return [self._batch_pass_fn(p) for p in self.plans]

    def eval_segment_batch(self, tensors: Sequence[TripleTensor]) -> list:
        """Evaluate ``B`` independent tensors in one dispatch; returns a
        list of per-tensor ``(counts, regs)`` in input order — the same
        pair ``eval_chunk`` yields, kept separate per tensor.

        The batch is padded with all-zero blocks up to a shard-count
        multiple and every block to one common 8-multiple row height;
        zero rows carry no flag bits, so padding is invisible to counters
        and sketches (asserted against per-tensor ``eval_chunk`` in
        tests/test_multidevice.py).
        """
        if not tensors:
            return []
        pad_b = (-len(tensors)) % self._shard_count()
        rows = max(8, max(((t.n_rows + 7) // 8) * 8 for t in tensors))
        with spans.span("scan.transfer"):
            stack = np.zeros((len(tensors) + pad_b, rows, N_PLANES),
                             np.int32)
            for i, t in enumerate(tensors):
                stack[i, :t.n_rows] = t.planes
            arr = jax.device_put(stack, self._sharding())
            arr.block_until_ready()
        spans.count("transfer.bytes", stack.nbytes)
        if self._count_form_sketches:
            spans.count("scan.sketch_count_rows",
                        self._count_form_sketches * stack.shape[0] * rows)
        with spans.span("scan.dispatch"):
            dispatched = [fn(arr) for fn in self._batch_pass_fns]
        # each batched output comes to the host once and is split there:
        # indexing the batch-sharded device array per segment would be one
        # device op and transfer per segment, and is a ShardingTypeError
        # on a mesh with explicit axes
        with spans.span("scan.wait"):
            outs = [(np.asarray(c, np.int64),
                     {k: np.asarray(v) for k, v in r.items()})
                    for c, r in dispatched]
        return [([c[i] for c, _ in outs],
                 {k: v[i] for _, r in outs for k, v in r.items()})
                for i in range(len(tensors))]

    @staticmethod
    def merge_chunk(state: dict, chunk_id: int, counts, regs) -> dict:
        """Idempotent merge — re-delivered chunks are ignored."""
        if chunk_id in state["chunks_done"]:
            return state
        with spans.span("scan.merge"):
            state["counts"] = [a + b
                               for a, b in zip(state["counts"], counts)]
            for k, v in regs.items():
                state["sketches"][k] = np.maximum(state["sketches"][k], v)
            state["chunks_done"].add(chunk_id)
        return state

    def finalize_state(self, state: dict, n_triples: int) -> AssessmentResult:
        with spans.span("scan.finalize"):
            # the banks go up by an explicit copy and the estimate runs
            # as one program: no implicit host-to-device transfer
            est = {"sketch:" + k: float(_hll_estimate(jax.device_put(v)))
                   for k, v in state["sketches"].items()}
            values: dict[str, float] = {}
            counts_out: dict[str, dict[str, int]] = {}
            for pln, counts in zip(self.plans, state["counts"]):
                values.update(pln.finalize(counts, est))
                for m in pln.metrics:
                    counts_out[m.name] = {
                        c: int(counts[pln.slots[m.name][c]])
                        for c, _ in m.counters}
        return AssessmentResult(values=values, counts=counts_out,
                                sketch_estimates=est, n_triples=n_triples,
                                passes=len(state["chunks_done"])
                                * self.passes_per_chunk,
                                registers={k: np.asarray(v) for k, v
                                           in state["sketches"].items()})


def run_single_shot(evaluator: QualityEvaluator,
                    tensor: TripleTensor) -> AssessmentResult:
    """One full-dataset pass per plan (one total when fused) — the
    single-shot execution path shared by ``QualityEvaluator.assess`` and
    the ``repro.qa`` pipeline.

    Expressed as a 1-chunk run through the mergeable-chunk interface, so
    single-shot and chunked execution share one finalize path and cannot
    drift apart.
    """
    with spans.run() as rec:
        state = evaluator.chunk_state_init()
        counts, regs = evaluator.eval_chunk(tensor)
        state = QualityEvaluator.merge_chunk(state, 0, counts, regs)
        result = evaluator.finalize_state(state, len(tensor))
    result.trace = rec
    return result
