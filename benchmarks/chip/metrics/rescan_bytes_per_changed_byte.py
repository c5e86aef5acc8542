"""Bytes the segment store rescanned (``ChunkStats.bytes_rescanned``,
summed over the window) per byte the changesets deleted or inserted."""


def read(run):
    changed = sum(s["changed_bytes"] for s in run.steps)
    if changed <= 0:
        return None
    return sum(s["stats"].bytes_rescanned for s in run.steps) / changed
