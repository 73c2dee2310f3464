"""The program's own spans in a profiler trace, and what they attribute.

`ServingEngine.step()` writes `engine.step` around each step (argument
`kind`: prefill, prefill_chunk, decode or spec_verify) and one span per
host phase inside it: `engine.admit`, `engine.blocks`, `engine.inputs`,
`engine.launch`, `engine.sample`, `engine.fetch`, `engine.emit`
(`src/repro/serving/tracing.py`). They sit on a thread line of
`/host:CPU`, inside the benchmark's `bench.step`, on the device trace's
clock.

Given the trace and its reduction (`trace_reduce.reduce`):
  spans(space)             each `engine.*` span as (name, start, end, kind);
  label_gaps(gaps, spans)  each idle gap relabelled with the innermost
                           `engine.*` span that covers its midpoint, where
                           one does (else its `bench.*` label stays);
  engine_idle_share        idle time put down to an `engine.*` span, in %
                           of the window: the part of `device_idle_share`
                           the engine's host code causes;
  decode_host_ms           mean over the window's decode `engine.step`
                           spans of the span less its `engine.fetch` time:
                           the host's serial work per decode step.

`bench/run.py` does not read these yet: that needs `trace_reduce.reduce`
to keep the engine's spans and a metric reader for each reading.
"""
from __future__ import annotations

import bisect
from typing import List, Optional, Tuple

from bench.trace_reduce import HOST_PLANE

PREFIX = "engine."
STEP = "engine.step"
FETCH = "engine.fetch"

Span = Tuple[str, float, float, Optional[str]]


def spans(space) -> List[Span]:
    """Every `engine.*` event of the host plane, by start."""
    out = []
    for plane in space.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    kind = dict(ev.stats).get("kind") \
                        if ev.name == STEP else None
                    out.append((ev.name, ev.start_ns, ev.end_ns, kind))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def label_gaps(gaps, engine_spans: List[Span]):
    """`gaps` as (start, end, label), each taking the innermost engine span
    that covers its midpoint. Spans of one thread nest, so the innermost is
    the top of a stack of the spans open at that instant."""
    order = sorted(range(len(gaps)), key=lambda i: gaps[i][0] + gaps[i][1])
    out = list(gaps)
    stack: List[Span] = []
    j = 0
    for i in order:
        s, e, label = gaps[i]
        t = (s + e) / 2
        while j < len(engine_spans) and engine_spans[j][1] <= t:
            while stack and stack[-1][2] < engine_spans[j][1]:
                stack.pop()
            stack.append(engine_spans[j])
            j += 1
        while stack and stack[-1][2] < t:
            stack.pop()
        out[i] = (s, e, stack[-1][0] if stack else label)
    return out


def engine_idle_share(red, engine_spans: List[Span]) -> Optional[float]:
    """Idle time whose innermost label is an `engine.*` span, in % of the
    window; None on a trace that holds no engine span."""
    if not engine_spans or red.window_s <= 0:
        return None
    idle = sum(e - s for s, e, label in label_gaps(red.gaps, engine_spans)
               if label.startswith(PREFIX))
    return 100.0 * idle / 1e9 / red.window_s


def decode_host_ms(red, engine_spans: List[Span]) -> Optional[float]:
    """Mean, over decode `engine.step` spans inside the window, of the
    span's length less the `engine.fetch` time inside it, in ms."""
    w0, w1 = red.window_ns
    steps = [(s, e) for n, s, e, kind in engine_spans
             if n == STEP and kind == "decode" and w0 <= s and e <= w1]
    if not steps:
        return None
    fetches = sorted((s, e) for n, s, e, _ in engine_spans if n == FETCH)
    starts = [s for s, _ in fetches]
    total = 0.0
    for s, e in steps:
        inside = fetches[bisect.bisect_left(starts, s):
                         bisect.bisect_right(starts, e)]
        total += (e - s) - sum(fe - fs for fs, fe in inside if fe <= e)
    return total / len(steps) / 1e6
