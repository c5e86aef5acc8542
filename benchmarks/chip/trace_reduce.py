"""Reduce a profiler trace of one window to what the metrics read.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
In it, each TPU chip is a plane ``/device:TPU:<n>``; its line
``XLA Ops`` holds one event per operation that ran, and its line
``XLA Modules`` one event per executable run, named after the jitted
function (``jit_local_pass(...)``).  The host plane ``/host:CPU`` holds
the benchmark's own ``bench.*`` spans, on the same clock.

* busy time: the union of the operation intervals of a chip, inside the
  window span (``bench.window``), averaged over the chips;
* idle share: 1 - busy / window;
* the longest idle gaps, each named by the innermost ``bench.*`` span
  around its middle;
* module time: the summed durations of the executables whose name starts
  with a given prefix, and how many ran.

An operation's event is named by its HLO text; the breakdown labels it by
its executable, its instruction name and opcode (and fusion kind):
``jit_local_pass %fusion.1 fusion/kCustom``.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re

WINDOW_SPAN = "bench.window"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
TOP = 10


_OPCODE = re.compile(r" ([a-z][\w-]*)\(")
_KIND = re.compile(r"kind=(k\w+)")


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    end_ns: float


def op_label(hlo: str) -> str:
    """``%fusion.1 fusion/kCustom`` from an operation's HLO text."""
    head, _, rest = hlo.partition(" = ")
    op = _OPCODE.search(" " + rest.split(" ", 1)[-1]) if rest else None
    kind = _KIND.search(rest)
    label = head + (" " + op.group(1) if op else "")
    return label + ("/" + kind.group(1) if kind else "")


def module_label(name: str) -> str:
    """``jit_local_pass`` from ``jit_local_pass(2580087564572038081)``."""
    return name.split("(", 1)[0]


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


@dataclasses.dataclass
class Trace:
    window: tuple[float, float]                 # ns, host clock
    ops: dict[str, list[Event]]                 # chip plane -> operations
    modules: dict[str, list[Event]]             # chip plane -> executables
    spans: list[Event]                          # bench.* host spans

    def _busy(self, plane: str) -> list[tuple[float, float]]:
        lo, hi = self.window
        return _union([(max(e.start_ns, lo), min(e.end_ns, hi))
                       for e in self.ops[plane]
                       if e.end_ns > lo and e.start_ns < hi])

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        if not self.ops:
            return 0.0
        total = sum(b - a for p in self.ops for a, b in self._busy(p))
        return total / len(self.ops) / 1e9

    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def idle_share(self) -> float | None:
        if not self.ops or self.window_s() <= 0:
            return None
        return 1.0 - self.busy_s() / self.window_s()

    def module_time(self, prefixes: tuple[str, ...]) -> tuple[float, int]:
        """(seconds, runs) of the executables named with one of
        ``prefixes``, inside the window, summed over the chips."""
        lo, hi = self.window
        hits = [e for evs in self.modules.values() for e in evs
                if e.name.startswith(prefixes)
                and e.start_ns >= lo and e.end_ns <= hi]
        return sum(e.end_ns - e.start_ns for e in hits) / 1e9, len(hits)

    def span_at(self, t_ns: float) -> str:
        inner = [s for s in self.spans if s.name != WINDOW_SPAN
                 and s.start_ns <= t_ns <= s.end_ns]
        if not inner:
            return "no bench span"
        return min(inner, key=lambda s: s.end_ns - s.start_ns).name

    def _module_at(self, plane: str, starts: list, t_ns: float) -> str:
        """The executable that ran the operation starting at ``t_ns``."""
        mods = self.modules.get(plane, [])
        i = bisect.bisect_right(starts, t_ns) - 1
        if i >= 0 and mods[i].end_ns >= t_ns:
            return module_label(mods[i].name)
        return "?"

    def breakdown(self) -> dict:
        """The operations that took most device time, and the longest idle
        gaps by the span the host was in (first chip), ``TOP`` each."""
        lo, hi = self.window
        per_op: collections.Counter = collections.Counter()
        for plane, evs in self.ops.items():
            starts = [m.start_ns for m in self.modules.get(plane, [])]
            for e in evs:
                if e.end_ns > lo and e.start_ns < hi:
                    label = (f"{self._module_at(plane, starts, e.start_ns)} "
                             f"{op_label(e.name)}")
                    per_op[label] += (min(e.end_ns, hi)
                                      - max(e.start_ns, lo)) / 1e9
        gaps = []
        if self.ops:
            busy = self._busy(sorted(self.ops)[0])
            edges = [lo] + [x for ab in busy for x in ab] + [hi]
            for a, b in zip(edges[::2], edges[1::2]):
                if b > a:
                    gaps.append((b - a, self.span_at((a + b) / 2)))
        gaps.sort(reverse=True)
        return {"device_ops": [[n, s] for n, s in per_op.most_common(TOP)],
                "idle_gaps": [[n, g / 1e9] for g, n in gaps[:TOP]]}


def find(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(
            f"expected one .xplane.pb under {trace_dir}, found {paths}")
    return paths[0]


def from_profile(pd) -> Trace:
    """A ``Trace`` from a ``jax.profiler.ProfileData``."""
    ops: dict[str, list[Event]] = {}
    modules: dict[str, list[Event]] = {}
    spans: list[Event] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                dest = {OPS_LINE: ops, MODULES_LINE: modules}.get(line.name)
                if dest is not None:
                    dest.setdefault(plane.name, []).extend(
                        Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans.extend(
                    Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events if e.name.startswith(SPAN_PREFIX))
    for evs in modules.values():
        evs.sort(key=lambda e: e.start_ns)
    windows = [s for s in spans if s.name == WINDOW_SPAN]
    if windows:
        window = (windows[0].start_ns, windows[0].end_ns)
    else:   # a trace without the window span: its whole extent
        every = [e for evs in ops.values() for e in evs] + spans
        window = (min(e.start_ns for e in every),
                  max(e.end_ns for e in every)) if every else (0.0, 0.0)
    return Trace(window, ops, modules, spans)


def load(trace_dir: str) -> Trace:
    import jax
    return from_profile(jax.profiler.ProfileData.from_file(find(trace_dir)))


def load_gz(path: str) -> Trace:
    """A ``Trace`` from a gzipped ``.xplane.pb``."""
    import gzip

    import jax
    with gzip.open(path, "rb") as f:
        return from_profile(
            jax.profiler.ProfileData.from_serialized_xspace(f.read()))
