"""jit'd public wrapper for the fused counts+sketches megakernel."""
from __future__ import annotations

import functools

from ...rdf.triple_tensor import COL_S_FLAGS
from .. import (ONEHOT_VMEM_BYTES, block_rows, fit_block, interpret_mode,
               record_scan)
from .. import onehot_row_cap as onehot_rows_for  # shared VMEM policy
from ..qap_count.ops import fused_count
from .kernel import fused_scan_kernel, vmem_bytes


def default_block_n(program, sketch_cols, p: int) -> int:
    """The largest row block whose VMEM footprint fits (``vmem_bytes``)."""
    return block_rows(functools.partial(vmem_bytes, program, sketch_cols, p))


def fused_scan(planes, program, n_counters: int,
               sketch_specs: tuple[tuple[str, tuple[int, ...]], ...],
               p: int, *, block_n: int | None = None,
               interpret: bool | None = None):
    """ONE pass over (N, P) planes → ((n_counters,) int32 counts,
    {sketch name: (2^p,) int32 registers}).

    Pads N up to a block multiple with zero rows — zero flag planes carry
    no VALID/KIND bits, so padding is invisible to every counter, and the
    kernel zeroes padded rows' ranks (s_flags == 0 ⇒ not a real row) so
    registers match the unpadded fold bit-for-bit.  ``block_n=None`` sizes
    the block from the VMEM model; ``interpret=None`` takes the platform's
    choice.

    Mesh-ready: traced inside ``shard_map`` (the evaluator's mesh path),
    ``planes`` is one device's row shard and the grid/blocking below is
    per-device — ``block_n`` shrinks to the local shard when small, and
    the zero-pad invisibility above is exactly what makes an uneven
    global row count (pad-to-device-multiple) safe: every device's
    counters/registers are computed as if the padding did not exist, so
    the cross-device ``psum``/``pmax`` equals the single-device scan.
    """
    if not sketch_specs:        # pure-counter plan: the qap_count kernel IS
        return (fused_count(planes, program, n_counters, block_n=block_n,
                            interpret=interpret), {})  # the one-pass scan
    record_scan(1)
    sketch_cols = tuple(cols for _, cols in sketch_specs)
    planes, block_n = fit_block(
        planes, block_n or default_block_n(program, sketch_cols, p))
    if interpret is None:
        interpret = interpret_mode()
    counts, regs = fused_scan_kernel(
        planes, program=program, n_counters=n_counters,
        sketch_cols=sketch_cols, p=p, valid_plane=COL_S_FLAGS,
        block_n=block_n, rows_tile=min(block_n, onehot_rows_for(p)),
        interpret=interpret)
    return counts[:n_counters], {name: r for (name, _), r
                                 in zip(sketch_specs, regs)}
