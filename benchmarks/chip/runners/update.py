"""Continuous re-assessment of one dataset under BSBM update transactions,
in a closed loop: one maintainer writes a changeset into the dataset file
and waits for the updated report before writing the next.

Traffic keys: ``triples``, the dataset's size, and ``dataset_seed``, which
draws the dataset: the same for every run, because the number of
content-defined segments, and with it the work of a changeset, follows
the bytes.  ``--seed`` draws the transactions.  Set-up writes the dump and
fills the segment store with one cold assessment, then compiles the
segment buckets a changeset can reach that the fill did not.  One step
draws the next transaction (one offer's ten statements deleted, one new
product with its offers and reviews appended), rewrites the file from the
first changed byte, and times the configuration's incremental assessment
of it.  Every answer is compared with a cold reference assessment of the
same version of the dataset.
"""
from __future__ import annotations

import os
import time

import jax
import numpy as np

from generators import bsbm
from reference import compare
from reference.assess import Assessment, control as lower
from reference.encoder import Encoder

SMALLEST_BUCKET = 1024          # the store pads a segment to a power of two


def bucket(rows: int) -> int:
    return max(SMALLEST_BUCKET, 1 << max(0, rows - 1).bit_length())


class Runner:
    def __init__(self, config: dict, traffic: dict, seed: int, work: str):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.n = int(traffic["triples"])
        self.path = os.path.join(work, "dataset.nt")
        self.store = os.path.join(work, "store")
        self.base_lines: list[str] = []
        self.changes: list[tuple] = []      # (at, deleted, inserted)

    def setup(self) -> None:
        from repro import qa
        from repro.rdf import TripleTensor
        from repro.store import iter_segments
        with jax.profiler.TraceAnnotation("bench.generate"):
            self.dump = bsbm.dump(self.n, self.traffic["dataset_seed"])
            self.base_lines = list(self.dump.lines)
            with open(self.path, "wb") as f:
                f.write(self.dump.data())
        self.txns = bsbm.transactions(self.dump, self.seed)
        target = int(self.config["segment_bytes"])
        self.pipe = (qa.pipeline().metrics(self.config["metrics"])
                     .base(*self.config["base_namespaces"])
                     .backend(self.config["backend"])
                     .hll(self.config["hll_p"])
                     .incremental(self.store, segment_bytes=target))
        with jax.profiler.TraceAnnotation("bench.ingest"):
            self.cold = self.pipe.run(self.path)
        # The fill compiled the bucket of each of its segments.  A
        # changeset's segments range from a short tail to two segments
        # merged where a deleted offer held a boundary.
        with open(self.path, "rb") as f:
            rows = [s.count(b"\n") for s in iter_segments(f, target)]
        self.buckets = []
        b = SMALLEST_BUCKET
        while b <= bucket(2 * max(rows)):
            self.buckets.append(b)
            b *= 2
        ev = self.pipe.evaluator()
        for b in sorted(set(self.buckets) - {bucket(r) for r in rows}):
            ev.eval_chunk(TripleTensor(np.zeros((b, 13), np.int32), 0))

    def describe(self) -> dict:
        st = self.cold.exec_stats
        return {"triples": self.n, "file_bytes": os.path.getsize(self.path),
                "segments": st.chunks_total, "buckets": self.buckets,
                "pipeline": repr(self.pipe)}

    def step(self) -> dict:
        with jax.profiler.TraceAnnotation("bench.changeset.write"):
            at, deleted, inserted = next(self.txns)
            self.changes.append((at, deleted, inserted))
            offset = sum(len(x) + 1 for x in self.dump.lines[:at])
            with open(self.path, "r+b") as f:
                f.seek(offset)
                f.write(("\n".join(self.dump.lines[at:]) + "\n").encode())
                f.truncate()
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.changeset.assess"):
            res = self.pipe.run(self.path)
        return {"latency_s": time.perf_counter() - t0,
                "version": len(self.changes), "triples": res.n_triples,
                "changed_bytes": sum(len(x) + 1 for x in deleted + inserted),
                "stats": res.exec_stats, "answer": res}

    def traced_extra(self) -> dict:
        return {}

    def notes(self, steps: list) -> list[str]:
        lat = [round(s["latency_s"], 4) for s in steps]
        resc = [s["stats"].segments_rescanned for s in steps]
        return [f"# changesets {len(steps)}; seconds each {lat}",
                f"# segments rescanned each {resc}"]

    def gaps(self, steps: list, control: bool = False) -> list[dict]:
        """Each answer against a cold reference assessment of its version:
        the reference encodes every line once and keeps, per version, the
        rows of the lines then in the file, in file order.  ``control``
        puts the reference itself, one precision lower than the configuration
        states (16-bit counters, one HyperLogLog bit less), in the place
        of every answer."""
        p = int(self.config["hll_p"])
        enc = Encoder(self.config["base_namespaces"])
        table = [enc.encode_lines(self.base_lines)]
        order = np.arange(len(self.base_lines))
        n_rows = len(self.base_lines)
        answers = {s["version"]: s["answer"] for s in steps}
        out = []
        for version, (at, deleted, inserted) in enumerate(self.changes, 1):
            order = np.delete(order, np.arange(at, at + len(deleted)))
            table.append(enc.encode_lines(inserted))
            order = np.concatenate(
                [order, np.arange(n_rows, n_rows + len(inserted))])
            n_rows += len(inserted)
            if version in answers:
                rows = np.concatenate(table)[order]
                answer = (lower(rows, p) if control
                          else answers[version])
                out.append(compare.gaps(answer, Assessment(rows, p)))
        return out

    def close(self) -> None:
        self.base_lines, self.changes, self.dump = [], [], None
