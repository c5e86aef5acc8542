"""HyperLogLog distinct-count sketches (beyond-paper action).

Luzzu *approximates* I2/CN2-style metrics for speed (paper §3.2 Correctness);
our dense engine computes them exactly — but true distinct-counts (distinct
triples, distinct predicates) need dedup, which on a 512-chip mesh would be a
giant all-to-all sort. HLL sketches make distinct-count a *mergeable* O(2^p)
register state: block-local updates, ``max``-merge across chunks/devices —
the same associativity that powers the fault-tolerance story (re-merging a
re-executed chunk is idempotent).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

DEFAULT_P = 12  # 4096 registers, ~1.6% relative error


def _fmix32(x: jnp.ndarray) -> jnp.ndarray:
    """murmur3 32-bit finalizer (uint32 lanes)."""
    x = x.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return x


def hash_columns(planes: jnp.ndarray, cols: tuple[int, ...],
                 salt: int = 0x9E3779B9) -> jnp.ndarray:
    """Combine int32 plane columns into one uint32 hash per row."""
    h = jnp.full((planes.shape[0],), jnp.uint32(salt))
    for c in cols:
        h = _fmix32(h ^ planes[:, c].astype(jnp.uint32))
        h = h * jnp.uint32(5) + jnp.uint32(0xE6546B64)
    return _fmix32(h)


def hll_init(p: int = DEFAULT_P) -> jnp.ndarray:
    return jnp.zeros((1 << p,), jnp.int32)


def rank_and_bucket(h: jnp.ndarray, p: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """bucket = top p bits; rank = 1 + clz of the remaining bits."""
    bucket = (h >> (32 - p)).astype(jnp.int32)
    w = (h << p).astype(jnp.uint32)
    max_rank = 32 - p + 1
    rank = jnp.where(w == 0, max_rank,
                     jax.lax.clz(w).astype(jnp.int32) + 1)
    rank = jnp.minimum(rank, max_rank)
    return bucket, rank


# The count form: C[b, r], the rows of bucket b and rank r, as one MXU
# product one_hot(hi)ᵀ · one_hot(lo·R + r − 1) over the rows, with
# b = hi·2^(p−h) + lo; a register is the largest r with C[b, r] > 0.
# Exact: only C > 0 is read, and a float32 sum of ones never rounds to 0.
# Its MXU work is 2 · 2^p · R a row and grows with p; the scatter's does
# not.  The TPU compiler's estimate of the fused dot (v5e at 1.5 GHz,
# 2^21 rows, one sketch, h = 6) is 2.1 ms at p = 12, 6.0 at 13, 8.9 at
# 14 and 14.4 at 15: 1.0, 2.9, 4.3 and 6.9 ns a row, against the ~7 ns
# a row the scatter takes at p = 12 (device trace of the pass on a v5e).
# So the count form folds up to p = 14, and the scatter, with no
# margin left at p = 15, above it.  h = 6 is the lowest estimate at
# p = 12 (h = 4..8 and R = 21, 24, 32 tried).
COUNT_FORM_MAX_P = 14
_HI_BITS = 6
# One-hot elements one step of the count form spans on the CPU, which
# materialises the one-hots: 2^15 rows a step at p = 12.
_CPU_STEP_ELEMS = 1 << 26


def _split(p: int) -> tuple[int, int, int]:
    """(h, bits of lo, R): the count matrix is 2^h × 2^(p−h)·R, R the
    rank cap 33 − p."""
    h = min(p, _HI_BITS)
    return h, p - h, 33 - p


def fuses_one_hots() -> bool:
    """Whether the platform's compiler fuses the count form's one-hots
    into its dot, so that they never reach memory: the TPU's does, the
    CPU's materialises them."""
    return jax.default_backend() != "cpu"


def _step_rows(n: int, width: int) -> int:
    """Rows one step of the count form folds: all ``n`` in one dot where
    the one-hots fuse into it (a loop there would make the rows a
    buffer of their own); else as many as span ``_CPU_STEP_ELEMS``
    one-hot elements of ``width``, a power of two."""
    if fuses_one_hots():
        return n
    return min(n, 1 << max(0, (_CPU_STEP_ELEMS // width).bit_length() - 1))


def _rank_counts(planes: jnp.ndarray, cols: tuple[int, ...], p: int,
                 valid: jnp.ndarray | None) -> jnp.ndarray:
    """C as a (2^h, 2^(p−h)·R) float32 matrix over these rows; a row that
    is not valid has key −1, whose one-hot is all zeros."""
    h, lo_bits, r_cap = _split(p)
    bucket, rank = rank_and_bucket(hash_columns(planes, cols), p)
    key = (bucket & ((1 << lo_bits) - 1)) * r_cap + rank - 1
    if valid is not None:
        key = jnp.where(valid, key, -1)
    return jax.lax.dot_general(
        jax.nn.one_hot(bucket >> lo_bits, 1 << h, dtype=jnp.bfloat16),
        jax.nn.one_hot(key, r_cap << lo_bits, dtype=jnp.bfloat16),
        (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)


def hll_update_scatter(registers: jnp.ndarray, planes: jnp.ndarray,
                       cols: tuple[int, ...], valid: jnp.ndarray | None = None
                       ) -> jnp.ndarray:
    """Fold a block of rows into the registers (scatter-max): the plain
    form, and the one above ``COUNT_FORM_MAX_P``."""
    p = int(np.log2(registers.shape[0]))
    h = hash_columns(planes, cols)
    bucket, rank = rank_and_bucket(h, p)
    if valid is not None:
        rank = jnp.where(valid, rank, 0)
    return registers.at[bucket].max(rank)


def uses_count_form(p: int) -> bool:
    """Whether ``hll_update`` folds 2^p registers by the count form."""
    return p <= COUNT_FORM_MAX_P


def hll_update(registers: jnp.ndarray, planes: jnp.ndarray,
               cols: tuple[int, ...], valid: jnp.ndarray | None = None
               ) -> jnp.ndarray:
    """Fold a block of rows into the registers: by the count form up to
    ``COUNT_FORM_MAX_P``, in ``_step_rows`` steps; by the scatter-max
    above it.  Both give the same registers bit for bit."""
    p = int(np.log2(registers.shape[0]))
    n = planes.shape[0]
    if not uses_count_form(p):
        return hll_update_scatter(registers, planes, cols, valid)
    if n == 0:
        return registers
    _, lo_bits, r_cap = _split(p)
    step = _step_rows(n, r_cap << lo_bits)
    n_full, tail = divmod(n, step)

    def fold(i, counts):
        rows = jax.lax.dynamic_slice_in_dim(planes, i * step, step)
        ok = (None if valid is None
              else jax.lax.dynamic_slice_in_dim(valid, i * step, step))
        return counts + _rank_counts(rows, cols, p, ok)

    counts = _rank_counts(planes[:step], cols, p,
                          None if valid is None else valid[:step])
    if n_full > 1:
        counts = jax.lax.fori_loop(1, n_full, fold, counts)
    if tail:
        counts = counts + _rank_counts(
            planes[n_full * step:], cols, p,
            None if valid is None else valid[n_full * step:])
    seen = counts.reshape(1 << p, r_cap) > 0
    top = jnp.max(jnp.where(seen, jnp.arange(1, r_cap + 1, dtype=jnp.int32),
                            0), axis=1)
    return jnp.maximum(registers, top)


def hll_merge(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return jnp.maximum(a, b)


def hll_estimate(registers: jnp.ndarray) -> jnp.ndarray:
    """Standard HLL estimator with small-range (linear counting) correction."""
    m = registers.shape[0]
    if m >= 128:
        alpha = 0.7213 / (1.0 + 1.079 / m)
    else:
        alpha = {16: 0.673, 32: 0.697, 64: 0.709}.get(m, 0.7213)
    inv = jnp.sum(jnp.exp2(-registers.astype(jnp.float32)))
    raw = alpha * m * m / inv
    zeros = jnp.sum(registers == 0)
    small = m * jnp.log(m / jnp.maximum(zeros, 1).astype(jnp.float32))
    return jnp.where((raw <= 2.5 * m) & (zeros > 0), small, raw)
