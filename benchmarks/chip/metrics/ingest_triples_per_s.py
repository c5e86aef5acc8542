"""Triples per second of ``Pipeline.ingest`` of the cell's whole file, in
one call after the traced window (host clock)."""


def read(run):
    s, n = run.extra.get("ingest_s"), run.extra.get("ingest_triples")
    if not s or not n:
        return None
    return n / s
