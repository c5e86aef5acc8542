#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, at a cell's own
size, in one process: the program's gaps on many seeds (the lower
readings), the control's on a few (the upper readings), and the program's
with a fault planted under the timed path.

    python3 benchmarks/chip/readings.py --workload bsbm_dump.encoded \\
        --seeds 11,12,13 --control-seeds 21,22,23 --fault half \\
        --fault-seeds 31,32,33 --steps 2

For each seed it sets the cell up as a run does, takes ``--steps`` units
of the cell's traffic through the timed path, and prints one JSON line of
the worst gaps from the reference and whether they are within the limits
(``correct``).  For each control seed it puts the reference itself, one
precision lower than the configuration states, in the place of the
program's answers.  For each fault seed it plants ``--fault`` (see
``faults.py``) before the set-up and removes it after.  Needs a TPU, like
a run.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

import run as harness


def seeds(text: str) -> list[int]:
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--steps", type=int, default=2)
    args = ap.parse_args(argv)
    _, cell, config, traffic = harness.load_cell(args.workload)
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    try:
        harness.find_chips(cell)
    except RuntimeError as e:
        harness.log(f"readings: {e}")
        return 1
    from repro.launch import enable_compile_cache

    import faults
    from reference import compare
    enable_compile_cache()
    runner_cls = harness.load_module("runners", traffic["runner"]).Runner
    work = os.path.join(harness.WORK, "readings")
    plan = ([(s, False, "") for s in seeds(args.seeds)]
            + [(s, True, "") for s in seeds(args.control_seeds)]
            + [(s, False, args.fault) for s in seeds(args.fault_seeds)])
    for seed, control, fault in plan:
        shutil.rmtree(work, ignore_errors=True)
        os.makedirs(work)
        undo = faults.plant(fault) if fault else None
        runner = runner_cls(config, traffic, seed, work)
        try:
            runner.setup()
            steps = [runner.step() for _ in range(args.steps)]
            gaps = compare.worst(runner.gaps(steps, control=control))
        finally:
            if undo is not None:
                undo()
            runner.close()
            shutil.rmtree(work, ignore_errors=True)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": control, "fault": fault or None,
                          "gaps": gaps, "correct": compare.within(gaps)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
