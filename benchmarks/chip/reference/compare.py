"""How far an assessment lies from the reference's: the numbers that
decide ``correct``.

* ``counts_gap`` — the largest absolute difference over every counter of
  every metric, and the number of triples.  A counter the answer lacks
  counts as the reference's whole value.
* ``registers_gap`` — HyperLogLog registers that differ, summed over the
  banks; a bank of another size, or a missing one, counts all of the
  reference's registers.
* ``values_gap`` — the largest relative difference over the metric values,
  against ``max(|reference|, 1e-12)``.  A missing value counts as 1.

``LIMITS`` are the largest gaps a correct run may show (see PERF.md for
the readings they were set from).
"""
from __future__ import annotations

import math

import numpy as np

LIMITS = {"counts_gap": 0, "registers_gap": 0, "values_gap": 1e-3}


def gaps(answer, ref) -> dict[str, float]:
    """Gaps of ``answer`` (anything with ``counts``, ``registers``,
    ``values`` and ``n_triples``) from the reference ``ref``."""
    counts_gap = abs(int(answer.n_triples) - ref.n_triples)
    for metric, cs in ref.counts.items():
        got = answer.counts.get(metric, {})
        for name, want in cs.items():
            counts_gap = max(counts_gap, abs(int(got.get(name, 0)) - want)
                             if name in got else abs(want))
    registers_gap = 0
    for name, want in ref.registers.items():
        got = answer.registers.get(name)
        got = None if got is None else np.asarray(got)
        if got is None or got.shape != want.shape:
            registers_gap += want.size
        else:
            registers_gap += int(np.count_nonzero(got != want))
    values_gap = 0.0
    for metric, want in ref.values.items():
        got = answer.values.get(metric)
        if got is None or not math.isfinite(float(got)):
            values_gap = max(values_gap, 1.0)
            continue
        values_gap = max(values_gap, abs(float(got) - want)
                         / max(abs(want), 1e-12))
    return {"counts_gap": counts_gap, "registers_gap": registers_gap,
            "values_gap": values_gap}


def worst(per_answer: list[dict]) -> dict[str, float]:
    """The largest of each gap over several answers."""
    out = {k: 0 for k in LIMITS}
    for g in per_answer:
        for k, v in g.items():
            out[k] = max(out[k], v)
    return out


def within(g: dict) -> bool:
    return all(g[k] <= LIMITS[k] for k in LIMITS)
