"""Triples assessed per second: every triple of every whole assessment in
the window, over the window (host clock)."""


def read(run):
    if not run.steps or run.window_s <= 0:
        return None
    return sum(s["triples"] for s in run.steps) / run.window_s
