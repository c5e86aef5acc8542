"""One-true-pass fused scan Pallas TPU megakernel.

Per ``(BLOCK_N, N_PLANES)`` int32 block resident in VMEM, ONE grid step
evaluates the planner's full counter bytecode (the ``qap_count`` stack
machine) AND folds the block into EVERY HLL sketch's register bank — so a
plan with S sketches costs exactly one HBM pass instead of ``1 + S``.

TPU mapping notes:

* accumulators live across grid steps with ``lambda i: (0, 0)`` index maps
  (init at step 0, ``+=`` / ``max``-merge afterwards): one
  ``(1, COUNTS_WIDTH)`` int32 counter row plus one
  ``(2^p // 128, 128)`` int32 register block per sketch.
* the murmur chain state is memoized per column *prefix*, so sketches whose
  column tuples share a prefix hash each shared column once per block.
  Since plane layout v2 the sketch tuples select the content-hash columns
  (``COL_S_HASH``/``COL_P_HASH``/``COL_O_HASH`` — e.g. ``(s_hash,)``,
  ``(s_hash, p_hash, o_hash)``); they participate in the chain like any
  other int32 plane, so the memoization is unchanged.
* the dense one-hot scatter-max — TPUs have no VPU scatter — is tiled over
  row sub-blocks of ``rows_tile`` so the ``(rows_tile, 2^p)`` intermediate
  stays inside a fixed VMEM budget at ANY ``p`` (the ops wrapper derives
  ``rows_tile`` from ``p``); ``BLOCK_N`` itself stays large for counter
  throughput.
* program/sketch specs are STATIC Python tuples — everything is unrolled at
  trace time; no dynamic control flow in the kernel.
* ``vmem_bytes`` is the block's scoped-VMEM model; the ops wrapper takes
  the largest power-of-two BLOCK_N it fits (``kernels.block_rows``).  For
  the ``all`` plan that is 1024 rows at p=12 and at p=14.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import LANE_ROW_BYTES, onehot_row_cap
from ..hll.kernel import _bucket_rank, _fmix32
from ..qap_count import kernel as qap_kernel
from ..qap_count.kernel import COUNTS_WIDTH, _eval_block

HASH_SALT = 0x9E3779B9  # same seed as core/sketches.py and kernels/hll


def _regs_block_shape(p: int) -> tuple[int, int]:
    """Lane-aligned (rows, lanes) layout for 2^p int32 registers."""
    m = 1 << p
    return (max(m // 128, 1), min(m, 128))


def vmem_bytes(program, sketch_cols, p: int, rows: int) -> int:
    """Scoped VMEM for a ``rows``-row block.  The counter phase and the
    sketch phase reuse each other's buffers (the compiler gives the
    ``all`` plan at 2048 rows the same figure as the counter kernel
    alone), so the need is the larger of the two: the counter stack
    machine's (``qap_count.kernel.vmem_bytes``), and the sketch phase's
    lane-padded row values — the double-buffered input block, the salt and
    one murmur state per distinct column prefix, a hash, bucket and rank
    per sketch, the invalid mask — beside one (rows_tile, 2^p) one-hot
    tile."""
    prefixes = {tuple(c[:i]) for c in sketch_cols
                for i in range(1, len(c) + 1)}
    slices = 2 + 1 + len(prefixes) + 3 * len(sketch_cols) + 1
    rows_tile = min(rows, onehot_row_cap(p))
    sketch = rows * LANE_ROW_BYTES * slices + rows_tile * (4 << p)
    return max(qap_kernel.vmem_bytes(program, rows), sketch)


def _sketch_update(block, cols, p, invalid, rows_tile, hash_states):
    """(BLOCK_N,) rows → (2^p,) block-local register maxima.

    ``hash_states`` memoizes the murmur chain per column prefix: sketches
    selecting overlapping column tuples share all common-prefix hash work.
    """
    def chain(prefix: tuple[int, ...]):
        if prefix not in hash_states:
            h = chain(prefix[:-1])
            c = prefix[-1]
            h = _fmix32(h ^ block[:, c:c + 1].astype(jnp.uint32))
            hash_states[prefix] = h * jnp.uint32(5) + jnp.uint32(0xE6546B64)
        return hash_states[prefix]

    h = _fmix32(chain(tuple(cols)))                    # (BLOCK_N, 1)
    bucket, rank = _bucket_rank(h, p)
    rank = jnp.where(invalid, 0, rank)                 # padding rows: rank 0

    # Tiled dense one-hot scatter-max: (rows_tile, 2^p) per tile keeps the
    # intermediate VMEM-bounded regardless of p.
    n_rows, m = block.shape[0], 1 << p
    acc = None
    for r0 in range(0, n_rows, rows_tile):
        sub_bucket = bucket[r0:r0 + rows_tile]
        sub_rank = rank[r0:r0 + rows_tile]
        lanes = jax.lax.broadcasted_iota(
            jnp.int32, (sub_bucket.shape[0], m), 1)
        hits = jnp.where(sub_bucket == lanes, sub_rank, 0)
        tile_max = jnp.max(hits, axis=0)               # (2^p,)
        acc = tile_max if acc is None else jnp.maximum(acc, tile_max)
    return acc


def _kernel(planes_ref, counts_ref, *regs_refs, program, n_counters,
            sketch_cols, p, rows_tile, valid_plane):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)
        for r in regs_refs:
            r[...] = jnp.zeros_like(r)

    block = planes_ref[...]                            # (BLOCK_N, P) int32

    # -- counters: the qap_count stack machine, unchanged -----------------
    partial = _eval_block(block, program, n_counters)
    vec = jnp.stack(partial)
    vec = jnp.pad(vec, (0, COUNTS_WIDTH - n_counters)).reshape(1, COUNTS_WIDTH)
    counts_ref[...] += vec

    # -- sketches: shared hash chain + tiled scatter-max ------------------
    n_rows = block.shape[0]
    hash_states = {(): jnp.full((n_rows, 1), jnp.uint32(HASH_SALT))}
    invalid = block[:, valid_plane:valid_plane + 1] == 0
    for cols, regs_ref in zip(sketch_cols, regs_refs):
        block_regs = _sketch_update(block, cols, p, invalid, rows_tile,
                                    hash_states)
        regs_ref[...] = jnp.maximum(regs_ref[...],
                                    block_regs.reshape(regs_ref.shape))


@functools.partial(
    jax.jit,
    static_argnames=("program", "n_counters", "sketch_cols", "p",
                     "valid_plane", "block_n", "rows_tile", "interpret"))
def fused_scan_kernel(planes, *, program, n_counters, sketch_cols, p,
                      valid_plane, block_n, rows_tile, interpret):
    """planes: (N, P) int32 with N % block_n == 0 →
    ((COUNTS_WIDTH,) int32 counts, tuple of (2^p,) int32 register banks,
    one per entry of ``sketch_cols``)."""
    n, width = planes.shape
    assert n % block_n == 0, (n, block_n)
    assert n_counters <= COUNTS_WIDTH
    assert sketch_cols, "use qap_count.fused_count when there are no sketches"
    rows, lanes = _regs_block_shape(p)
    n_sketches = len(sketch_cols)
    out = pl.pallas_call(
        functools.partial(_kernel, program=program, n_counters=n_counters,
                          sketch_cols=sketch_cols, p=p, rows_tile=rows_tile,
                          valid_plane=valid_plane),
        grid=(n // block_n,),
        in_specs=[pl.BlockSpec((block_n, width), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((1, COUNTS_WIDTH), lambda i: (0, 0))]
        + [pl.BlockSpec((rows, lanes), lambda i: (0, 0))] * n_sketches,
        out_shape=[jax.ShapeDtypeStruct((1, COUNTS_WIDTH), jnp.int32)]
        + [jax.ShapeDtypeStruct((rows, lanes), jnp.int32)] * n_sketches,
        interpret=interpret,
    )(planes)
    counts = out[0][0]
    regs = tuple(r.reshape(1 << p) for r in out[1:])
    return counts, regs
