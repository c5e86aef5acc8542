"""A BSBM dataset held whole in the devices' memory, row-sharded over a
mesh of the configuration's ``chips``, re-assessed back to back.

Traffic keys: ``base_triples``, the base dump (encoded by the reference
encoder), and ``tiles``, the renamed copies of it that make the dataset
(``generators/tiles.py``).  Set-up encodes the base, starts the reference
(folded over every tile in worker processes while the window runs),
builds the tiles on the devices, checks the first and last tile of each
device against the NumPy form, and assesses once.  One step is one whole
assessment of the resident planes through ``Pipeline.shard(mesh).run``:
the planes never cross from the host, and only counters and registers
come back.  Every step assesses the same dataset, so the reference is
made once and compared with every answer.
"""
from __future__ import annotations

import os
import time

import jax
import numpy as np

from generators import bsbm, tiles
from reference import compare, tiled
from runners.dump import pipeline

COUNTERS = ("transfer.bytes", "resident.bytes", "scan.blocks")


class Runner:
    def __init__(self, config: dict, traffic: dict, seed: int, work: str):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.n_tiles = int(traffic["tiles"])
        self.chips = int(config["chips"])
        self.path = os.path.join(work, "base.npz")
        self.base = None
        self.target = None
        self.reference = None       # tiled.Pending

    def setup(self) -> None:
        from repro.launch.mesh import make_assessment_mesh
        from repro.rdf import TripleTensor
        with jax.profiler.TraceAnnotation("bench.generate"):
            d = bsbm.dump(int(self.traffic["base_triples"]), self.seed)
            self.base = tiles.base(d.lines, self.config["base_namespaces"])
            del d
            self.base.save(self.path)
            self.reference = tiled.Pending(self.path, self.n_tiles,
                                           int(self.config["hll_p"]))
            mesh = make_assessment_mesh(self.chips)
            planes = tiles.device_tiles(self.base, self.n_tiles, mesh)
            planes.block_until_ready()
            self.check(planes)
        self.target = TripleTensor(planes, self.n_tiles * self.base.rows,
                                   self.n_tiles * self.base.n_terms)
        self.pipe = pipeline(self.config).shard(mesh)
        self.step()                 # warm: the one shape of the window

    def check(self, planes) -> None:
        """The first and the last tile each device holds, read back and
        compared with the NumPy form."""
        per = self.n_tiles // self.chips
        for t in sorted({t for d in range(self.chips)
                         for t in (d * per, d * per + per - 1)}):
            if not np.array_equal(tiles.device_tile(planes, self.base, t),
                                  tiles.tile(self.base, t)):
                raise RuntimeError(f"tile {t} on the device differs from "
                                   f"its NumPy form")

    def describe(self) -> dict:
        rows = self.target.n_rows
        return {"tiles": self.n_tiles, "base_triples": self.base.rows,
                "triples": len(self.target), "chips": self.chips,
                "rows_per_chip": rows // self.chips,
                "plane_bytes": rows * self.target.planes.shape[1] * 4,
                "pipeline": repr(self.pipe)}

    def step(self) -> dict:
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.assess"):
            res = self.pipe.run(self.target)
        return {"latency_s": time.perf_counter() - t0,
                "triples": res.n_triples, "stats": res.exec_stats,
                "answer": res}

    def traced_extra(self) -> dict:
        return {"rows_per_chip": self.target.n_rows // self.chips}

    def notes(self, steps: list) -> list[str]:
        lat = sorted(s["latency_s"] for s in steps)
        out = [f"# assessments {len(steps)}; seconds each: min "
               f"{lat[0]:.4f}, median {lat[len(lat) // 2]:.4f}, max "
               f"{lat[-1]:.4f}" if lat else "# no assessment finished"]
        if steps:
            counts = steps[0]["answer"].trace.counts
            out.append("# counters an assessment: " + ", ".join(
                f"{k} {counts.get(k, 0)}" for k in COUNTERS))
        folded = self.reference.result()
        out.append(f"# reference: {self.n_tiles} tiles folded by "
                   f"{self.reference.workers} workers in "
                   f"{self.reference.seconds:.3f} s; "
                   f"{folded.n_triples} triples")
        return out

    def gaps(self, steps: list, control: bool = False) -> list[dict]:
        """Each answer's gaps from the reference.  ``control`` puts the
        reference itself, one precision lower than the configuration
        states (16-bit counters, one HyperLogLog bit less), in the place
        of every answer."""
        ref = self.reference.result()
        if control:
            return [compare.gaps(tiled.control(
                self.path, self.n_tiles, int(self.config["hll_p"])), ref)]
        return [compare.gaps(s["answer"], ref) for s in steps]

    def close(self) -> None:
        if self.reference is not None:
            self.reference.close()
        self.base = self.target = self.reference = None
