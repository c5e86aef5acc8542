"""Triples of the window's assessments over the summed self time of the
program's ``ingest.*`` spans (read, tokenize, fallback, dedup, intern,
planes): the ingest layer's own rate inside the pipelined runs that are
timed, whatever of it overlaps the device."""
from program_spans import recorders, self_seconds


def read(run):
    steps = [s for s in run.steps
             if getattr(s.get("answer"), "trace", None) is not None]
    seconds = self_seconds(recorders(run), "ingest.")
    if not steps or seconds <= 0:
        return None
    return sum(s["triples"] for s in steps) / seconds
