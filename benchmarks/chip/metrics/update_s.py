"""Seconds per changeset: the window over the changesets completed in it
(host clock, closed loop)."""


def read(run):
    if not run.steps:
        return None
    return run.window_s / len(run.steps)
