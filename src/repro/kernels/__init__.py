"""Pallas TPU kernels for the paper's compute hot spots.

* ``qap_count`` — fused multi-metric predicate+count scan (the paper's metric
  evaluation loop, one HBM pass for all metrics).
* ``hll`` — HyperLogLog register update (distinct-count actions).
* ``fused_scan`` — the one-true-pass megakernel: counter bytecode AND every
  HLL sketch's register bank updated per VMEM-resident block, so sketch
  metrics no longer cost one extra HBM scan each.

Kernels target TPU (pl.pallas_call + BlockSpec VMEM tiling).  Whether
``pallas_call`` compiles or interprets is decided once, from the platform
JAX runs on (``interpret_mode``): the CPU interprets, which is how the
kernels are validated against the pure numpy/jnp oracles in ``*/ref.py``;
the TPU compiles.

Pass accounting
---------------
Every op wrapper that launches a kernel (or jnp scan) streaming the full
planes tensor HBM→VMEM once calls ``record_scan()``.  Wrappers run at trace
time, so tracing one pass function under ``count_scans()`` counts its HBM
data passes per execution — the hook behind
``QualityEvaluator.passes_per_chunk`` and the pass-count assertions in
``tests/test_qa.py``.

VMEM model
----------
A kernel's row block must fit the scoped VMEM the TPU compiler grants
without being asked for more (``SCOPED_VMEM_BYTES``).  The planes block is
``(rows, 13)`` int32, and it, every ``(rows, 1)`` column slice, mask and
hash the kernels compute fill whole ``(8, 128)`` tiles: each costs
``LANE_ROW_BYTES`` per row whatever its width.  ``qap_count`` and
``fused_scan`` count the lane-padded values they hold at once
(``vmem_bytes``), and ``block_rows`` takes the largest power-of-two block
that fits; the ``hll`` fold's block is bounded by its one-hot
(``onehot_row_cap``).  ``tests/test_tpu_compile.py`` compiles the chosen
blocks for a v5e chip.
"""
from __future__ import annotations

import contextlib
import threading

import jax
import jax.numpy as jnp

SCOPED_VMEM_BYTES = 16 << 20        # TPU compiler's default scoped limit
LANE_ROW_BYTES = 128 * 4            # one int32 row of a (8, 128) tile

# VMEM budget for the dense (rows, 2^p) one-hot scatter-max intermediate —
# the HLL kernels' sizing constraint (TPUs have no VPU scatter).  One
# policy for both the standalone ``hll`` fold and the ``fused_scan``
# megakernel's internal row tiling; ``fused_scan.kernel.vmem_bytes``
# counts it beside the lane-padded row values.
ONEHOT_VMEM_BYTES = 4 << 20


def onehot_row_cap(p: int) -> int:
    """Largest 8-multiple row count whose (rows, 2^p) int32 one-hot fits
    the VMEM budget (floors at the 8-row tile: p=12 → 256, p=14 → 64)."""
    return max(8, (ONEHOT_VMEM_BYTES // (4 << p)) // 8 * 8)


def block_rows(vmem_bytes, cap: int = 1 << 16) -> int:
    """Largest power-of-two row block in ``[8, cap]`` for which
    ``vmem_bytes(rows)`` fits ``SCOPED_VMEM_BYTES``."""
    rows = 8
    while rows < cap and vmem_bytes(2 * rows) <= SCOPED_VMEM_BYTES:
        rows *= 2
    return rows


def fit_block(planes, block_n: int):
    """→ (planes, block_n) for a kernel grid: ``block_n`` shrinks to the
    8-row-aligned input for inputs smaller than one block, and rows pad
    with zeros up to a block multiple.  Zero rows carry no flag bits, so
    padding is invisible to every counter and sketch."""
    n = planes.shape[0]
    block_n = min(block_n, max(8, -(-n // 8) * 8))
    pad = (-n) % block_n
    if pad:
        planes = jnp.pad(planes, ((0, pad), (0, 0)))
    return planes, block_n


def interpret_mode() -> bool:
    """Whether ``pallas_call`` runs in the interpreter, from the platform
    JAX runs on: the CPU interprets, the TPU compiles.  Any other platform
    has no kernel path and raises."""
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(
        f"the Pallas kernels run on TPU (or interpreted on CPU), not on "
        f"{platform!r}; use backend='jnp'")


class _ScanCounter(threading.local):
    active = False
    count = 0


_scans = _ScanCounter()


def record_scan(n: int = 1) -> None:
    """Declare ``n`` full passes over the planes tensor (called by op
    wrappers at trace time; a no-op unless inside ``count_scans()``)."""
    if _scans.active:
        _scans.count += n


@contextlib.contextmanager
def count_scans():
    """Count ``record_scan`` calls in this thread; yields a 1-element list
    whose slot holds the running (and, on exit, final) count."""
    prev_active, prev_count = _scans.active, _scans.count
    _scans.active, _scans.count = True, 0
    box = [0]
    try:
        yield box
        box[0] = _scans.count
    finally:
        _scans.active, _scans.count = prev_active, prev_count
