"""Segment dictionary footprints replayed per changeset: the mean of
``ChunkStats.footprints_replayed`` over the window."""


def read(run):
    if not run.steps:
        return None
    return (sum(s["stats"].footprints_replayed for s in run.steps)
            / len(run.steps))
