"""Seconds per changeset that the segment store spent writing: freezing
the rescanned segments' states, committing the manifest and appending
the quality history (self time of the program's ``store.freeze``,
``store.commit`` and ``store.history`` spans)."""
from program_spans import per_step


def read(run):
    return per_step(run, "store.freeze", "store.commit", "store.history")
