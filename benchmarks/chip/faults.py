"""Faults planted under the timed path: each leaves a run that has to
come out not correct.  The self-tests plant them at tiny sizes, and
``readings.py --fault`` at a cell's own size on the chip.

* ``half`` — half of every batch left out: the second half of the rows
  handed to the device are zeroed, so no metric sees them;
* ``altered`` — an answer altered where it is produced: one counter of
  every chunk's result is off by one;
* ``stale`` — a step that returns its state unchanged: every incremental
  assessment answers with the first one's result.
"""
from __future__ import annotations

from typing import Callable

import numpy as np

NAMES = ("half", "altered", "stale")


def plant(name: str) -> Callable[[], None]:
    """Plant the fault ``name``; returns a function that removes it."""
    if name == "half":
        from repro.core.evaluator import QualityEvaluator as cls
        attr = "device_planes"
        real = cls.device_planes

        def fault(self, tensor):
            planes = np.array(tensor.planes)
            planes[planes.shape[0] // 2:] = 0     # rows no metric can see
            return real(self, type(tensor)(planes, tensor.n_valid,
                                           tensor.n_terms))
    elif name == "altered":
        from repro.core.evaluator import QualityEvaluator as cls
        attr = "materialize_chunk"
        real = cls.materialize_chunk

        def _altered(outs):
            counts, regs = real(outs)
            counts[0] = counts[0].copy()
            counts[0][0] += 1
            return counts, regs
        fault = staticmethod(_altered)
    elif name == "stale":
        import repro.store as cls
        attr = "assess_incremental"
        real = cls.assess_incremental
        first = []

        def fault(*a, **kw):
            if not first:
                first.append(real(*a, **kw))
            return first[0]
    else:
        raise ValueError(f"no fault {name!r}; there are {NAMES}")
    saved = cls.__dict__[attr]
    setattr(cls, attr, fault)
    return lambda: setattr(cls, attr, saved)
