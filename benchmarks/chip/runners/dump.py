"""Bulk assessment of a whole BSBM dump, back to back.

Traffic keys: ``triples``, the dump's size, and ``input``: ``ntriples``
(the dump as an N-Triples file on disk, assessed by the configuration's
streamed, pipelined pipeline) or ``encoded`` (the same dump encoded into
planes by the reference encoder in set-up and held in host memory,
assessed single-shot).  One step is one whole assessment; every step
assesses the same dump, so the reference is made once and compared with
every answer.
"""
from __future__ import annotations

import os
import time

import jax

from generators import bsbm
from reference import compare
from reference.assess import Assessment, control as lower
from reference.encoder import Encoder


def pipeline(config: dict):
    """The configuration's pipeline, before its execution mode."""
    from repro import qa
    return (qa.pipeline().metrics(config["metrics"])
            .base(*config["base_namespaces"]).backend(config["backend"])
            .hll(config["hll_p"]))


class Runner:
    def __init__(self, config: dict, traffic: dict, seed: int, work: str):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.n = int(traffic["triples"])
        self.path = os.path.join(work, "dump.nt")
        self.lines = None           # the dump's lines (N-Triples input)
        self.planes = None          # the dump's planes (encoded input)
        self.target = None

    def setup(self) -> None:
        from repro.rdf import TripleTensor
        pipe = pipeline(self.config)
        with jax.profiler.TraceAnnotation("bench.generate"):
            d = bsbm.dump(self.n, self.seed)
            if self.traffic["input"] == "ntriples":
                self.lines = d.lines
                with open(self.path, "wb") as f:
                    f.write(d.data())
                self.target = self.path
                self.pipe = pipe.streamed(
                    self.config["stream_chunk_triples"]).pipelined()
            else:
                enc = Encoder(self.config["base_namespaces"])
                self.planes = enc.encode_lines(d.lines)
                self.target = TripleTensor(self.planes, self.n, enc.n_terms)
                self.pipe = pipe
        self.step()                 # warm: every shape of the window

    def describe(self) -> dict:
        out = {"input": self.traffic["input"], "triples": self.n,
               "pipeline": repr(self.pipe)}
        if self.lines is not None:
            out["file_bytes"] = os.path.getsize(self.path)
        else:
            out["plane_bytes"] = int(self.planes.nbytes)
        return out

    def step(self) -> dict:
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.assess"):
            res = self.pipe.run(self.target)
        return {"latency_s": time.perf_counter() - t0,
                "triples": res.n_triples, "stats": res.exec_stats,
                "answer": res}

    def rows_per_scan(self) -> int:
        if self.lines is not None:
            return min(self.n, int(self.config["stream_chunk_triples"]))
        return self.n

    def traced_extra(self) -> dict:
        """After a traced window: the scan's row count, and for N-Triples
        input the ingest of the whole file through ``Pipeline.ingest``."""
        out = {"rows_per_scan": self.rows_per_scan()}
        if self.lines is not None:
            t0 = time.perf_counter()
            tt = pipeline(self.config).ingest(self.path)
            out["ingest_s"] = time.perf_counter() - t0
            out["ingest_triples"] = len(tt)
        return out

    def notes(self, steps: list) -> list[str]:
        lat = sorted(s["latency_s"] for s in steps)
        slow = sorted(range(len(steps)), key=lambda i: steps[i]["latency_s"])
        out = [f"# assessments {len(steps)}; seconds each: min "
               f"{lat[0]:.4f}, median {lat[len(lat) // 2]:.4f}, max "
               f"{lat[-1]:.4f}; slowest (step, s) "
               f"{[(i, round(steps[i]['latency_s'], 4)) for i in slow[-5:]]}"
               if lat else "# no assessment finished"]
        st = steps[0]["stats"] if steps else None
        if st is not None:
            out.append(f"# chunks {st.chunks_total}, passes_per_chunk "
                       f"{st.passes_per_chunk}, mode {st.mode}")
        return out

    def gaps(self, steps: list, control: bool = False) -> list[dict]:
        """Each answer's gaps from the reference.  ``control`` puts the
        reference itself, one precision lower than the configuration
        states (16-bit counters, one HyperLogLog bit less), in the place
        of every answer."""
        p = int(self.config["hll_p"])
        planes = self.planes
        if self.lines is not None:
            planes = Encoder(self.config["base_namespaces"]).encode_lines(
                self.lines)
        ref = Assessment(planes, p)
        if control:
            return [compare.gaps(lower(planes, p), ref)]
        return [compare.gaps(s["answer"], ref) for s in steps]

    def close(self) -> None:
        self.lines = self.planes = self.target = None
