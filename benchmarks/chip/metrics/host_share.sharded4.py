"""Share of the window in which the host, and not the chips, set the
pace of the resident re-assessments: self time of every program span
(``repro.*``: placing the planes, dispatch, merge, finalize and the root's
own) except ``scan.wait``, where the host waits for the chips, over the
window."""
from program_spans import recorders, self_seconds

WAIT = "scan.wait"


def read(run):
    recs = recorders(run)
    if not recs or run.window_s <= 0:
        return None
    host = sum(r.self_seconds() for r in recs) - self_seconds(recs, WAIT)
    return host / run.window_s
