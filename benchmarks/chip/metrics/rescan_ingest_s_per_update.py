"""Seconds per changeset spent encoding the rescanned segments: self time
of the program's ``ingest.*`` spans inside the store's runs."""
from program_spans import per_step


def read(run):
    return per_step(run, "ingest.")
