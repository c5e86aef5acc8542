"""Seconds per changeset of dictionary footprint replay: self time of the
program's ``store.replay`` spans."""
from program_spans import per_step


def read(run):
    return per_step(run, "store.replay")
