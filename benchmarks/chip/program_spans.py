"""What the metric readers take from the program's own spans: the
``repro.spans.Recorder`` that each answer of the window carries as
``trace``.  A program that records no spans gives no recorders, and the
readers that need them read nothing."""


def recorders(run) -> list:
    """The recorders of the window's steps, in step order."""
    out = []
    for step in run.steps:
        rec = getattr(step.get("answer"), "trace", None)
        if rec is not None:
            out.append(rec)
    return out


def self_seconds(recs: list, *prefixes: str) -> float:
    """Self time of the spans named with any of ``prefixes``, summed over
    ``recs``."""
    return sum(r.self_seconds(p) for r in recs for p in prefixes)


def counter(recs: list, name: str) -> int:
    return sum(r.counts.get(name, 0) for r in recs)


def per_step(run, *prefixes: str):
    """Self seconds of ``prefixes`` per step, or None without spans."""
    recs = recorders(run)
    if not recs:
        return None
    return self_seconds(recs, *prefixes) / len(recs)
