"""The row-sharded pass's share of its roofline, in percent.

The least time is that of each chip reading its own rows of the planes
once at the chip's HBM peak (``peaks.json``): rows a chip x 13 planes x
4 B for each run of the mesh pass executable (``jit_mesh_pass``) on each
chip.  The time is the summed device time of those runs over every chip,
so the share is the chips' mean.
"""
PASS_MODULES = ("jit_mesh_pass",)
BYTES_PER_ROW = 13 * 4


def read(run):
    if run.trace is None or not run.extra.get("rows_per_chip"):
        return None
    seconds, runs = run.trace.module_time(PASS_MODULES)
    if runs == 0 or seconds <= 0:
        return None
    least = (runs * run.extra["rows_per_chip"] * BYTES_PER_ROW
             / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
