"""Spans and per-step counters of `ServingEngine.step()`.

One mechanism gives two outputs. `StepTracer.phase(name)` opens a profiler
span `engine.<name>` (a `jax.profiler.TraceAnnotation`, i.e. a TraceMe: it
lands in the profiler's trace on the device trace's clock, and costs a
no-op check when no profiler runs) and adds the phase's *self* time, read
on the engine's injected clock, to the open step's `host` record. A phase
opened inside another phase is taken out of the outer one's time, so the
seven numbers of a step never count the same instant twice and sum to at
most the step's `dt`. Under a `VirtualClock` every phase reads 0.

The root span `engine.step` encloses one step; when the step writes a
`step_log` entry the span carries its `kind` and its `index` into
`step_log` as arguments, which joins a trace to the entry's `rids`.

Phases (each also a span name, `engine.<phase>`):
  admit   admission planning: expiry, prefix-cache lookup, reclaim,
          block allocation, scheduler bookkeeping, slot placement
  blocks  paged block management before a decode: chain growth,
          copy-on-write, speculative scratch leases
  inputs  host arrays built and uploaded: token rows, last tokens,
          lengths, block tables, prefix ids, scatter indices
  launch  each call of a jitted program or eager device op; it returns
          before the device finishes, and a compile shows up here
  sample  key split and sampling dispatch (argmax for greedy drafts)
  fetch   every device-to-host copy the step blocks on
  emit    tokens appended, completions, slot frees, prefix-cache insert,
          the step record written

`compiles` counts backend compiles in this process during the step, from
one process-wide `jax.monitoring` listener registered on first use.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional

import jax

PHASES = ("admit", "blocks", "inputs", "launch", "sample", "fetch", "emit")
SPANS = {p: f"engine.{p}" for p in PHASES}
STEP_SPAN = "engine.step"

_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compiles = 0
_listening = False


def _on_duration(event: str, duration: float, **_):
    global _compiles
    if event == _BACKEND_COMPILE_EVENT:
        _compiles += 1


class _Phase:
    __slots__ = ("tracer", "name", "span", "t0", "inner")

    def __init__(self, tracer: "StepTracer", name: str):
        self.tracer = tracer
        self.name = name

    def __enter__(self):
        self.span = jax.profiler.TraceAnnotation(SPANS[self.name])
        self.span.__enter__()
        self.inner = 0.0
        self.tracer._open.append(self)
        self.t0 = self.tracer.clock()
        return self

    def __exit__(self, *exc):
        tr = self.tracer
        took = tr.clock() - self.t0
        tr._open.pop()
        tr.host[self.name] += took - self.inner
        if tr._open:
            tr._open[-1].inner += took
        self.span.__exit__(*exc)
        return False


class StepTracer:
    """The engine's spans and the `host`/`compiles` record of the step in
    progress. `begin()` and `end()` bracket one step; a phase opened
    outside a step still writes its span, and its time goes nowhere."""

    def __init__(self, clock: Callable[[], float]):
        global _listening
        if not _listening:
            jax.monitoring.register_event_duration_secs_listener(_on_duration)
            _listening = True
        self.clock = clock
        self.host: Dict[str, float] = dict.fromkeys(PHASES, 0.0)
        self._open = []
        self._root = None
        self._c0 = 0

    def phase(self, name: str) -> _Phase:
        return _Phase(self, name)

    def begin(self):
        self.host = dict.fromkeys(PHASES, 0.0)
        self._c0 = _compiles
        self._root = jax.profiler.TraceAnnotation(STEP_SPAN)
        self._root.__enter__()

    def end(self, rec: Optional[Dict], index: int):
        """Close the step: `rec` (the step's `step_log` entry at `index`,
        or None when the step wrote none) gains `host` and `compiles`."""
        if rec is not None:
            rec["host"] = self.host
            rec["compiles"] = _compiles - self._c0
            self._root.set_metadata(kind=rec["kind"], index=index)
        self._root.__exit__(None, None, None)
        self._root = None
        self.host = dict.fromkeys(PHASES, 0.0)
