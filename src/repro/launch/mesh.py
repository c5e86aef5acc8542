"""Mesh construction.

Functions, not module-level constants, so importing this module never
touches jax device state.
"""
from __future__ import annotations

import jax


def make_host_mesh(model: int = 1):
    """Small mesh over whatever devices exist (CPU tests, examples)."""
    n = len(jax.devices())
    assert n % model == 0, (n, model)
    return jax.make_mesh((n // model, model), ("data", "model"))


def make_assessment_mesh(devices: int = 0):
    """1-D data-parallel mesh for quality assessment (row sharding only —
    the evaluator splits chunk rows over every axis).  ``devices=0`` uses
    all visible devices; pass an explicit count to use a subset (e.g. a
    1→N scalability sweep)."""
    n = devices or len(jax.devices())
    avail = len(jax.devices())
    if not 1 <= n <= avail:
        raise ValueError(f"devices must be in [1, {avail}], got {n}")
    return jax.make_mesh((n,), ("data",), devices=jax.devices()[:n])
