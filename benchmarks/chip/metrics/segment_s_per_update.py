"""Seconds per changeset that the segment store spent cutting the file
into content-defined segments and fingerprinting them: self time of the
program's ``store.segment`` and ``store.fingerprint`` spans."""
from program_spans import per_step


def read(run):
    return per_step(run, "store.segment", "store.fingerprint")
