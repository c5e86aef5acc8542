"""Spans and counters of one run, on the profiler's clock.

``span(name)`` times one stage of the program (a block, a chunk or a
segment, never a line).  It enters ``jax.profiler.TraceAnnotation(
"repro." + name)``, so under the JAX profiler the stage lands on the
trace's host plane beside the device's operations, and it appends
``(name, start_ns, end_ns, parent, thread)`` to the ``Recorder`` of the
run the calling thread works for.  ``count(name, n)`` adds to that
recorder's counters.  ``run()`` opens a run: the root span ``qa.run``
with a new recorder, or, inside a run already open on the thread, that
run's recorder.  A thread started for a run records into it after
``attach(handle())`` — thread-local state does not cross
``threading.Thread``.  Outside a run a span still times itself and
annotates the profiler, and records nothing.

Times are ``time.time_ns()``, the clock the profiler stamps host events
with.  XLA compiles are recorded as ``compile`` spans under the innermost
open span of the compiling thread, from JAX's monitoring events.
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import NamedTuple, Optional

import jax

PREFIX = "repro."
ROOT = "qa.run"
COMPILE = "compile"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Span(NamedTuple):
    name: str
    start_ns: int
    end_ns: int          # -1 while open
    parent: int          # index into Recorder.spans; -1 for the root
    thread: int


class Recorder:
    """The spans and counters of one run; ``spans[0]`` is the root."""

    def __init__(self):
        self.spans: list[Span] = []
        self.counts: dict[str, int] = {}
        self._lock = threading.Lock()

    def _add(self, span: Span) -> int:
        with self._lock:
            self.spans.append(span)
            return len(self.spans) - 1

    def _close(self, i: int, end_ns: int) -> None:
        with self._lock:
            self.spans[i] = self.spans[i]._replace(end_ns=end_ns)

    def count(self, name: str, n: int) -> None:
        with self._lock:
            self.counts[name] = self.counts.get(name, 0) + int(n)

    def _self_ns(self) -> list[tuple[str, int]]:
        """(name, self time) of each closed span: its duration less the
        part of its interval that its children, on any thread, cover."""
        with self._lock:
            spans = list(self.spans)
        kids: dict[int, list[Span]] = {}
        for s in spans:
            if s.parent >= 0 and s.end_ns >= 0:
                kids.setdefault(s.parent, []).append(s)
        out = []
        for i, s in enumerate(spans):
            if s.end_ns < 0:
                continue
            covered, reach = 0, s.start_ns
            for c in sorted(kids.get(i, ()), key=lambda c: c.start_ns):
                a, b = max(c.start_ns, reach), min(c.end_ns, s.end_ns)
                if b > a:
                    covered += b - a
                    reach = b
            out.append((s.name, s.end_ns - s.start_ns - covered))
        return out

    def self_seconds(self, prefix: str = "") -> float:
        """Summed self time of the closed spans named with ``prefix``."""
        return sum(ns for name, ns in self._self_ns()
                   if name.startswith(prefix)) / 1e9

    def self_seconds_by_name(self) -> dict[str, float]:
        out: dict[str, float] = {}
        for name, ns in self._self_ns():
            out[name] = out.get(name, 0.0) + ns / 1e9
        return out


class _ThreadState(threading.local):
    recorder: Optional[Recorder] = None
    stack: tuple = ()            # indices of this thread's open spans


_state = _ThreadState()
_listener_lock = threading.Lock()
_listening = False


def _on_time_span(event: str, start_s: float, end_s: float, **_) -> None:
    rec = _state.recorder
    if event == _COMPILE_EVENT and rec is not None:
        parent = _state.stack[-1] if _state.stack else -1
        rec._add(Span(COMPILE, int(start_s * 1e9), int(end_s * 1e9), parent,
                      threading.get_ident()))


def _listen_for_compiles() -> None:
    global _listening
    with _listener_lock:
        if not _listening:
            jax.monitoring.register_event_time_span_listener(_on_time_span)
            _listening = True


class span:
    """``with span("scan.wait") as s: ...`` — ``s.seconds`` after it."""

    __slots__ = ("name", "start_ns", "end_ns", "_annotation", "_rec", "_i")

    def __init__(self, name: str):
        self.name = name
        self.start_ns = self.end_ns = 0

    def __enter__(self) -> "span":
        self._annotation = jax.profiler.TraceAnnotation(PREFIX + self.name)
        self._annotation.__enter__()
        self._rec = _state.recorder
        self.start_ns = time.time_ns()
        if self._rec is not None:
            stack = _state.stack
            self._i = self._rec._add(Span(
                self.name, self.start_ns, -1, stack[-1] if stack else -1,
                threading.get_ident()))
            _state.stack = stack + (self._i,)
        return self

    def __exit__(self, *exc) -> None:
        self.end_ns = time.time_ns()
        if self._rec is not None:
            self._rec._close(self._i, self.end_ns)
            _state.stack = _state.stack[:-1]
        self._annotation.__exit__(*exc)

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9


def count(name: str, n: int) -> None:
    """Add ``n`` to the counter ``name`` of the run on this thread."""
    rec = _state.recorder
    if rec is not None:
        rec.count(name, n)


@contextlib.contextmanager
def run():
    """Open a run on this thread and yield its recorder; inside a run
    already open here, yield that run's recorder and open nothing."""
    rec = _state.recorder
    if rec is not None:
        yield rec
        return
    _listen_for_compiles()
    rec = Recorder()
    _state.recorder, _state.stack = rec, ()
    try:
        with span(ROOT):
            yield rec
    finally:
        _state.recorder, _state.stack = None, ()


def handle() -> tuple:
    """This thread's run and innermost open span, for ``attach``."""
    return _state.recorder, (_state.stack[-1] if _state.stack else -1)


@contextlib.contextmanager
def attach(h: tuple):
    """Record this thread's spans into the run of ``h`` (from
    ``handle()`` on the thread that started this one), as children of the
    span that was open there."""
    saved = _state.recorder, _state.stack
    rec, parent = h
    _state.recorder = rec
    _state.stack = (parent,) if rec is not None and parent >= 0 else ()
    try:
        yield
    finally:
        _state.recorder, _state.stack = saved
