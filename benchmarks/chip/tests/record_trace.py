"""Record a small profiler trace of the assessment path, for the trace
reduction's test fixture, and print how the trace is laid out.

    python benchmarks/chip/tests/record_trace.py OUT_DIR

Runs a streamed assessment of a small N-Triples text and one ``qa.assess``
of small encoded planes, each inside a ``bench.*`` span, under
``jax.profiler.trace``.  Prints every plane, its lines, their event counts
and the commonest event names, then copies the ``.xplane.pb`` to
``OUT_DIR/trace.xplane.pb``.
"""
from __future__ import annotations

import collections
import glob
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.abspath(os.path.join(HERE, "..", "..", ".."))


def main(out_dir: str) -> int:
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    from repro import qa
    from repro.rdf import bsbm_ntriples, synth_encoded

    dev = jax.devices()[0]
    print(f"# device {dev.platform} {dev.device_kind} x "
          f"{len(jax.devices())}; {os.cpu_count()} host cores")
    text = bsbm_ntriples(2000, seed=1).encode()
    tt = synth_encoded(1 << 16, seed=1)
    pipe = qa.pipeline().metrics("all").base("http://bsbm.example.org/")
    # warm: compile outside the traced window
    pipe.streamed(4096).pipelined().run(text)
    qa.assess(tt, metrics="all")
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    with jax.profiler.trace(tmp, profiler_options=opts):
        with jax.profiler.TraceAnnotation("bench.assess"):
            pipe.streamed(4096).pipelined().run(text)
        with jax.profiler.TraceAnnotation("bench.assess"):
            qa.assess(tt, metrics="all")
    path = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                     recursive=True)[0]
    pd = jax.profiler.ProfileData.from_file(path)
    for plane in pd.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            names = collections.Counter(e.name for e in evs)
            print(f"  line {line.name!r}: {len(evs)} events; "
                  f"{names.most_common(6)}")
            for e in evs[:2]:
                print(f"    e {e.name!r} start_ns={e.start_ns} "
                      f"dur_ns={e.duration_ns} stats={dict(e.stats)}")
    os.makedirs(out_dir, exist_ok=True)
    shutil.copy(path, os.path.join(out_dir, "trace.xplane.pb"))
    print(f"# {os.path.getsize(path)} bytes -> {out_dir}")
    shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
