"""The plain reference over a tiled dataset (``generators/tiles.py``),
folded tile by tile: ``assess.counters`` summed in int64 and
``assess.registers`` max-merged over every tile, then ``assess.values``.

No process holds the whole dataset: worker processes each build their
tiles in NumPy from the saved base and fold them.  They are spawned, and
import NumPy and this package only, never JAX, so that no second TPU
runtime loads in the process tree.  ``Pending`` returns at once; the
answer is read with ``Pending.result``, so the fold can run while the
program is measured.
"""
from __future__ import annotations

import multiprocessing
import os
import time

import numpy as np

from . import vocab
from .assess import counters, registers, values
from .encoder import COL_S_FLAGS

MAX_WORKERS = 16
WAIT_S = 1800                   # the longest a fold may take


class Folded:
    """The reference's answer for a tiled dataset, as ``assess.Assessment``
    gives it for one held whole."""

    def __init__(self, n_triples: int, counts: dict, regs: dict,
                 counter_dtype=np.int64):
        self.n_triples = n_triples
        self.counts = {m: {c: int(np.array(v).astype(counter_dtype))
                           for c, v in cs.items()}
                       for m, cs in counts.items()}
        self.registers = regs
        self.values = values(self.counts, self.registers)


def _merge(acc: tuple, part: tuple) -> tuple:
    """Two (valid rows, counters, registers) folded into one (``acc``'s
    dictionaries are updated in place)."""
    n, counts, regs = acc
    for m, cs in part[1].items():
        for k, v in cs.items():
            counts[m][k] += v
    for k, v in part[2].items():
        np.maximum(regs[k], v, out=regs[k])
    return n + part[0], counts, regs


def fold(path: str, tiles: list[int], p: int) -> tuple:
    """(valid rows, counters, registers) of ``tiles`` of the base saved
    at ``path``: the work of one worker."""
    from generators.tiles import Base, tile
    b = Base.load(path)
    # column-major, so that every plane the counters read is contiguous:
    # twice as fast as row-major on the CPU
    b.planes = np.asfortranarray(b.planes)
    acc = None
    for t in tiles:
        planes = tile(b, t)
        part = (int(np.count_nonzero(planes[:, COL_S_FLAGS] & vocab.VALID)),
                counters(planes), registers(planes, p))
        acc = part if acc is None else _merge(acc, part)
    return acc


class Pending:
    """The reference for ``n_tiles`` tiles of the base saved at ``path``:
    a fold under way in a pool of spawned workers."""

    def __init__(self, path: str, n_tiles: int, p: int,
                 counter_dtype=np.int64):
        self.counter_dtype = counter_dtype
        workers = max(1, min(MAX_WORKERS, n_tiles, os.cpu_count() or 1))
        self.t0 = time.perf_counter()
        self.seconds = None     # start to the last worker's answer
        self._folded = None
        self._pool = multiprocessing.get_context("spawn").Pool(workers)
        self._async = self._pool.starmap_async(
            fold, [(path, list(range(w, n_tiles, workers)), p)
                   for w in range(workers)], callback=self._done)
        self.workers = workers

    def _done(self, _) -> None:
        self.seconds = time.perf_counter() - self.t0

    def result(self) -> Folded:
        if self._folded is None:
            parts = self._async.get(WAIT_S)
            acc = parts[0]
            for part in parts[1:]:
                acc = _merge(acc, part)
            self._folded = Folded(*acc, self.counter_dtype)
        return self._folded

    def close(self) -> None:
        self._pool.terminate()
        self._pool.join()


def control(path: str, n_tiles: int, p: int) -> Folded:
    """The reference one precision lower: 16-bit counters, ``p - 1``."""
    pending = Pending(path, n_tiles, p - 1, counter_dtype=np.int16)
    try:
        return pending.result()
    finally:
        pending.close()
