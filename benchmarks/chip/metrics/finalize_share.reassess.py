"""Share of the window the host spent merging chunk results and
finalizing them (HyperLogLog estimates included): self time of the
program's ``scan.merge`` and ``scan.finalize`` spans over the window."""
from program_spans import recorders, self_seconds


def read(run):
    recs = recorders(run)
    if not recs or run.window_s <= 0:
        return None
    return self_seconds(recs, "scan.merge", "scan.finalize") / run.window_s
