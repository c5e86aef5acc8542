"""jnp reference path for the fused scan — the same one-logical-pass
contract (counts + every sketch register bank from one planes argument),
built from the independently-tested reference pieces: the bytecode
interpreter (``core.expr.eval_program_jnp``) and the sketch update
(``core.sketches.hll_update``).  Bit-identical to the megakernel;
``tests/test_kernels.py`` holds both to it."""
from __future__ import annotations

import jax.numpy as jnp

from ...core import sketches as hll
from ...core.expr import eval_program_jnp
from ...rdf.triple_tensor import COL_S_FLAGS


def fused_scan_jnp(planes, program, n_counters: int,
                   sketch_specs: tuple[tuple[str, tuple[int, ...]], ...],
                   p: int):
    """((n_counters,) int32 counts, {name: (2^p,) int32 registers})."""
    counts = eval_program_jnp(planes, program, n_counters)
    valid = planes[:, COL_S_FLAGS] != 0   # any flag bit ⇒ real row
    regs = {name: hll.hll_update(hll.hll_init(p), planes, cols, valid=valid)
            for name, cols in sketch_specs}
    return counts, regs
