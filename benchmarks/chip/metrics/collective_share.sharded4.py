"""Share of the row-sharded pass's device time spent in its all-reduce
operations (the ``psum`` of the counters and the ``pmax`` of the register
banks, their start and done parts included): on each chip, the summed
durations of those operations inside the runs of ``jit_mesh_pass`` in the
window, over the summed durations of those runs; the mean over the chips
that ran the pass."""
from trace_reduce import op_label

PASS_MODULES = ("jit_mesh_pass",)
COLLECTIVE = "all-reduce"


def _collective(hlo: str) -> bool:
    label = op_label(hlo)
    name, _, opcode = label.partition(" ")
    return name.startswith("%" + COLLECTIVE) or opcode.startswith(COLLECTIVE)


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace.window
    shares = []
    for plane, mods in run.trace.modules.items():
        runs = [(m.start_ns, m.end_ns) for m in mods
                if m.name.startswith(PASS_MODULES)
                and m.start_ns >= lo and m.end_ns <= hi]
        total = sum(b - a for a, b in runs)
        if total <= 0:
            continue
        inside = sum(e.end_ns - e.start_ns
                     for e in run.trace.ops.get(plane, [])
                     if _collective(e.name)
                     and any(a <= e.start_ns <= b for a, b in runs))
        shares.append(inside / total)
    return sum(shares) / len(shares) if shares else None
