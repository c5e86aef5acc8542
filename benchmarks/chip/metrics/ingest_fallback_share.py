"""Share of the ingested lines that the tokenizer's fast path handed to
the per-line reference parser: the program's ``ingest.fallback_lines``
over ``ingest.lines``, summed over the window's assessments."""
from program_spans import counter, recorders


def read(run):
    recs = recorders(run)
    lines = counter(recs, "ingest.lines")
    if lines <= 0:
        return None
    return counter(recs, "ingest.fallback_lines") / lines
