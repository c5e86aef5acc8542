"""Share of the traced window in which no operation ran on a chip
(1 - union of operation intervals / window, the mean over the chips), in
re-assessments of a dataset resident and row-sharded over four chips."""


def read(run):
    return None if run.trace is None else run.trace.idle_share()
