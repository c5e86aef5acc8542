"""HyperLogLog register-update Pallas TPU kernel.

Per block of rows: murmur-finalizer hash of the selected plane columns →
(bucket, rank) → scatter-max into 2^p registers.  The kernel is
column-agnostic; since plane layout v2 the distinct-count sketches select
the content-hash planes (``COL_*_HASH``), which makes the resulting
register banks invariant to term-id renumbering. TPUs have no native
scatter-max in the VPU, so the kernel uses the dense one-hot formulation:

    regs_block[m] = max_i rank[i] * [bucket[i] == m]

The (BLOCK_N, M) intermediate is the VMEM sizing constraint — the ops
wrapper derives BLOCK_N from p (``ops.bounded_block_n``) so it stays inside
a fixed VMEM budget at any p; rows stream HBM→VMEM once. Registers are an
(M//128, 128) int32 accumulator block reused across grid steps (init at step
0, max-merge afterwards) — merging is associative, which is exactly what the
fault-tolerance layer relies on.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _fmix32(x):
    x = x.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return x


# the bucket/rank split is shape-generic pure jnp — reuse the ONE
# derivation from core/sketches so the kernels and the jnp scatter path
# cannot diverge (the megakernel imports it from here too)
from ...core.sketches import rank_and_bucket as _bucket_rank


def _kernel(planes_ref, regs_ref, *, cols, p, valid_plane):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        regs_ref[...] = jnp.zeros_like(regs_ref)

    block = planes_ref[...]            # (BLOCK_N, P) int32
    n_rows = block.shape[0]
    m = 1 << p

    h = jnp.full((n_rows, 1), jnp.uint32(0x9E3779B9))
    for c in cols:
        h = _fmix32(h ^ block[:, c:c + 1].astype(jnp.uint32))
        h = h * jnp.uint32(5) + jnp.uint32(0xE6546B64)
    h = _fmix32(h)

    bucket, rank = _bucket_rank(h, p)                 # (BLOCK_N, 1) each
    if valid_plane is not None:
        rank = jnp.where(block[:, valid_plane:valid_plane + 1] != 0, rank, 0)

    # Dense one-hot scatter-max: (BLOCK_N, M) — the VMEM working set.
    lanes = jax.lax.broadcasted_iota(jnp.int32, (n_rows, m), 1)
    hits = jnp.where(bucket == lanes, rank, 0)        # (BLOCK_N, M)
    block_regs = jnp.max(hits, axis=0)                # (M,)
    regs_ref[...] = jnp.maximum(regs_ref[...],
                                block_regs.reshape(regs_ref.shape))


@functools.partial(
    jax.jit,
    static_argnames=("cols", "p", "valid_plane", "block_n", "interpret"))
def hll_fold_kernel(planes, *, cols, p, valid_plane, block_n, interpret):
    """planes: (N, P) int32, N % block_n == 0 → (2^p,) int32 registers."""
    n, width = planes.shape
    assert n % block_n == 0, (n, block_n)
    m = 1 << p
    rows = max(m // 128, 1)
    out = pl.pallas_call(
        functools.partial(_kernel, cols=cols, p=p, valid_plane=valid_plane),
        grid=(n // block_n,),
        in_specs=[pl.BlockSpec((block_n, width), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows, min(m, 128)), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, min(m, 128)), jnp.int32),
        interpret=interpret,
    )(planes)
    return out.reshape(m)
