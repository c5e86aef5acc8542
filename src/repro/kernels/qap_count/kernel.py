"""Fused QAP predicate+count Pallas TPU kernel.

The paper's hot loop — predicate evaluation + count over the main dataset —
is memory-bandwidth bound (≪1 FLOP/byte), so the kernel's job is: stream the
``(N, N_PLANES)`` int32 planes HBM→VMEM once, evaluate EVERY metric counter's
predicate bytecode on the VMEM-resident block with VPU integer ops, and
accumulate K partial counts in a VMEM accumulator that lives across grid
steps. One data pass for all metrics (vs. the paper's one pass per metric).

TPU mapping notes:
* block = (BLOCK_N, N_PLANES) int32.  In VMEM its 13 columns pad to the
  128 lanes of a (8, 128) int32 tile, and so does every (BLOCK_N, 1)
  column slice and mask the stack machine makes: each costs 512 B per row.
  ``vmem_bytes`` counts what the kernel holds at once — the double-buffered
  input block, one slice per plane the program reads, the stack, the VALID
  mask and one temporary — and the ops wrapper takes the largest
  power-of-two BLOCK_N that fits the compiler's scoped VMEM
  (``kernels.block_rows``).  For the ``all`` plan (8 planes, depth 3) that
  is 15 slices, 7.5 KiB per row, and BLOCK_N = 2048.
* the bytecode is STATIC (a Python tuple) — the stack machine is fully
  unrolled at trace time; there is no dynamic control flow in the kernel.
* the counter accumulator is a (1, COUNTS_WIDTH) int32 VMEM block with a
  ``None``-style index map (same block every grid step): initialized at step
  0, ``+=`` afterwards — the canonical Pallas reduction pattern.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...core.expr import (OP_AND, OP_ANYBITS, OP_EMIT, OP_EQ, OP_EQP, OP_GE,
                          OP_GT, OP_HASBITS, OP_LE, OP_LT, OP_NE, OP_NOT,
                          OP_OR, program_stack_depth)
from .. import LANE_ROW_BYTES

COUNTS_WIDTH = 128  # lane-aligned counter row; supports up to 128 counters


def vmem_slices(program) -> int:
    """Lane-padded (rows, 1) values the stack machine holds at once: the
    double-buffered input block (2), one slice per plane ``program``
    reads, its stack, the VALID mask and one temporary."""
    planes = set()
    for op, a, b in program:
        if op not in (OP_AND, OP_OR, OP_NOT, OP_EMIT):
            planes.add(a)
            if op == OP_EQP:
                planes.add(b)
    return 2 + len(planes) + program_stack_depth(program) + 2


def vmem_bytes(program, rows: int) -> int:
    """Scoped VMEM the kernel needs for a ``rows``-row block."""
    return rows * LANE_ROW_BYTES * vmem_slices(program)


def _eval_block(block, program, n_counters):
    """Unrolled stack machine over one (BLOCK_N, P) int32 block.

    Masks are (BLOCK_N, 1) int32 (0/1) — 2D keeps TPU vector layouts happy.
    Returns a list of K scalar partial counts.
    """
    stack = []
    counts = [jnp.int32(0)] * n_counters

    def col(a):
        return block[:, a:a + 1]  # (BLOCK_N, 1)

    from ...core.expr import VALID_BIT, VALID_PLANE
    valid = ((col(VALID_PLANE) & jnp.int32(VALID_BIT)) != 0
             ).astype(jnp.int32)  # padding rows count in no metric

    for op, a, b in program:
        if op == OP_HASBITS:
            m = jnp.int32(b)
            stack.append(((col(a) & m) == m).astype(jnp.int32))
        elif op == OP_ANYBITS:
            stack.append(((col(a) & jnp.int32(b)) != 0).astype(jnp.int32))
        elif op == OP_LT:
            stack.append((col(a) < b).astype(jnp.int32))
        elif op == OP_LE:
            stack.append((col(a) <= b).astype(jnp.int32))
        elif op == OP_GT:
            stack.append((col(a) > b).astype(jnp.int32))
        elif op == OP_GE:
            stack.append((col(a) >= b).astype(jnp.int32))
        elif op == OP_EQ:
            stack.append((col(a) == b).astype(jnp.int32))
        elif op == OP_NE:
            stack.append((col(a) != b).astype(jnp.int32))
        elif op == OP_EQP:
            stack.append((col(a) == col(b)).astype(jnp.int32))
        elif op == OP_AND:
            y = stack.pop(); x = stack.pop()
            stack.append(x & y)  # 0/1 ints: & == logical and
        elif op == OP_OR:
            y = stack.pop(); x = stack.pop()
            stack.append(x | y)
        elif op == OP_NOT:
            stack.append(jnp.int32(1) - stack.pop())
        elif op == OP_EMIT:
            counts[a] = counts[a] + jnp.sum(stack.pop() * valid,
                                            dtype=jnp.int32)
        else:
            raise ValueError(f"bad opcode {op}")
    assert not stack, "unbalanced bytecode"
    return counts


def _kernel(planes_ref, counts_ref, *, program, n_counters):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)

    partial = _eval_block(planes_ref[...], program, n_counters)
    vec = jnp.stack(partial)  # (K,)
    vec = jnp.pad(vec, (0, COUNTS_WIDTH - n_counters)).reshape(1, COUNTS_WIDTH)
    counts_ref[...] += vec


@functools.partial(
    jax.jit,
    static_argnames=("program", "n_counters", "block_n", "interpret"))
def fused_count_kernel(planes, *, program, n_counters, block_n, interpret):
    """planes: (N, P) int32 with N % block_n == 0 → (COUNTS_WIDTH,) int32."""
    n, p = planes.shape
    assert n % block_n == 0, (n, block_n)
    assert n_counters <= COUNTS_WIDTH
    grid = (n // block_n,)
    out = pl.pallas_call(
        functools.partial(_kernel, program=program, n_counters=n_counters),
        grid=grid,
        in_specs=[pl.BlockSpec((block_n, p), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((1, COUNTS_WIDTH), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, COUNTS_WIDTH), jnp.int32),
        interpret=interpret,
    )(planes)
    return out[0]
