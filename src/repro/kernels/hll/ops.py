"""jit'd wrapper for the HLL fold kernel."""
from __future__ import annotations

from ...rdf.triple_tensor import COL_S_FLAGS
from .. import (ONEHOT_VMEM_BYTES, fit_block, interpret_mode, onehot_row_cap,
               record_scan)
from .kernel import hll_fold_kernel


def bounded_block_n(p: int, block_n: int) -> int:
    """Cap ``block_n`` so the (BLOCK_N, 2^p) int32 one-hot fits the shared
    VMEM budget at ANY ``p`` (the un-capped default of 1024 rows at p=14
    would be 64 MiB)."""
    return min(block_n, onehot_row_cap(p))


def hll_fold(planes, cols: tuple[int, ...], p: int, *,
             block_n: int = 1024, interpret: bool | None = None):
    """Fold (N, P) planes into (2^p,) HLL registers.

    Row validity is derived from the s_flags plane directly (zero ⇒ padding
    row), avoiding a second streamed input; this matches the jnp path's
    ``valid = planes[:, COL_S_FLAGS] != 0``.  ``interpret=None`` takes
    the platform's choice.
    """
    record_scan(1)
    planes, block_n = fit_block(planes, bounded_block_n(p, block_n))
    if interpret is None:
        interpret = interpret_mode()
    return hll_fold_kernel(planes, cols=tuple(cols), p=p,
                           valid_plane=COL_S_FLAGS, block_n=block_n,
                           interpret=interpret)
