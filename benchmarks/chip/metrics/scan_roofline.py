"""The device scan's share of its roofline, in percent.

The least time is that of reading the planes once at the chip's HBM peak
(``peaks.json``): rows x 13 planes x 4 B per row for each pass
executable that ran.  The scan is bound by bytes: its integer predicate
and hash work has no published peak to set against.  The time is the
summed device time of the pass executables in the trace, so the share
holds whatever backend or number of passes makes up the scan.
"""
PASS_MODULES = ("jit_local_pass",)
BYTES_PER_ROW = 13 * 4


def read(run):
    if run.trace is None or not run.extra.get("rows_per_scan"):
        return None
    seconds, runs = run.trace.module_time(PASS_MODULES)
    if runs == 0 or seconds <= 0:
        return None
    least = (runs * run.extra["rows_per_scan"] * BYTES_PER_ROW
             / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
