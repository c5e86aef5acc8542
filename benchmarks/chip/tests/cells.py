"""Run a cell of the benchmark at a tiny size on whatever JAX finds,
skipping the harness's look for a chip."""
from __future__ import annotations

import tempfile

import run as harness

# each test process works in a directory of its own, so that test workers
# running one cell at once do not share its work directory
harness.WORK = tempfile.mkdtemp(prefix="bench-tests-work-")

TINY = {"bsbm_dump.ntriples": {"triples": 8192},
        "bsbm_dump.encoded": {"triples": 8192},
        "bsbm_update.changesets": {"triples": 8192}}
TINY_CONFIG = {"stream_chunk_triples": 2048, "segment_bytes": 65536}


def tiny(name: str):
    """(BENCHMARK.json, cell, configuration, traffic) cut to test size."""
    bench, cell, config, traffic = harness.load_cell(name)
    config = {k: TINY_CONFIG.get(k, v) for k, v in config.items()}
    return bench, cell, config, dict(traffic, **TINY[name])


def run_tiny(name: str, seed: int = 2**31 + 5, seconds: float = 1.0,
             trace: bool = False) -> dict:
    import jax
    bench, cell, config, traffic = tiny(name)
    peaks = harness.load_json(harness.HERE, "peaks.json")["devices"][
        "TPU v5 lite"]
    return harness.execute(bench, cell, config, traffic, seed, seconds,
                           trace, jax.devices(), peaks)
