"""jit'd public wrapper around the fused count kernel (pads + dispatches)."""
from __future__ import annotations

import functools

from .. import block_rows, fit_block, interpret_mode, record_scan
from .kernel import fused_count_kernel, vmem_bytes


def default_block_n(program) -> int:
    """The largest row block whose VMEM footprint fits (``vmem_bytes``)."""
    return block_rows(functools.partial(vmem_bytes, program))


def fused_count(planes, program, n_counters: int, *,
                block_n: int | None = None, interpret: bool | None = None):
    """Evaluate the fused bytecode over (N, P) planes → (n_counters,) int32.

    Pads N up to a block multiple with zero rows — zero flag planes carry no
    VALID/KIND bits, so padding is invisible to every well-formed predicate.
    ``block_n=None`` sizes the block from the VMEM model; ``interpret=None``
    takes the platform's choice.
    """
    record_scan(1)
    planes, block_n = fit_block(planes, block_n or default_block_n(program))
    if interpret is None:
        interpret = interpret_mode()
    counts = fused_count_kernel(planes, program=program,
                                n_counters=n_counters, block_n=block_n,
                                interpret=interpret)
    return counts[:n_counters]
