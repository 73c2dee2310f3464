"""From a profiler trace of the measured window to device numbers.

The JAX profiler writes an XSpace (`*.xplane.pb`). Device planes are named
`/device:TPU:<n>`; their "XLA Ops" line holds one event per operation that
ran, with its start and length in nanoseconds on the same clock as the host
planes. An event's name is the op's HLO text: `%quant_matmul.43 =
bf16[6144,3584]{...} custom-call(bf16[6144,18944]{...} %x, s8[18944,3584]
...)`, so a Pallas kernel is named after the jitted wrapper that calls it and
carries its operand shapes. A `while` (a scan over layers) is an event that
encloses its body's events. The benchmark's own host spans (`bench.window`,
`bench.step`, `bench.submit`, `bench.wait`) sit on a thread line of
`/host:CPU`.

`reduce(space)` returns:
  window_ns      the `bench.window` span (start, end);
  busy_s         union of the device ops' intervals inside it, averaged over
                 the device planes;
  op_seconds     device self seconds (an op's time less that of the ops it
                 encloses) by short op name (`short_name`), inside the window;
  ops            (HLO text, start, end) of each op that encloses no other;
  gaps           every interval of the window no device op covers, as
                 (start, end, host span covering its midpoint).
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Reduced:
    window_ns: Tuple[float, float]
    busy_s: float
    op_seconds: Dict[str, float]
    ops: List[tuple]
    gaps: List[tuple]

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) / 1e9


def find_xspace(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


_TYPE = re.compile(r"\b(bf16|f32|s32|s8|u8|pred)\[([0-9,]*)\]")


def short_name(hlo: str) -> str:
    """`%quant_matmul.43 = bf16[8,3584]{...} custom-call(...)` ->
    `quant_matmul bf16[8,3584]`: the op without its instance number, and
    its result type."""
    head, _, rest = hlo.partition(" = ")
    base = re.sub(r"\.\d+$", "", head.lstrip("%"))
    m = _TYPE.search(rest)
    return f"{base} {m.group(0)}" if m else base


def shapes(hlo: str):
    """(dtype, dims) of the result and then of each operand, in order."""
    return [(t, tuple(int(x) for x in d.split(",") if x))
            for t, d in _TYPE.findall(hlo)]


def _self_times(events):
    """Self nanoseconds of nested (start, end, i) events, and which of them
    enclose another."""
    self_ns = [e - s for s, e, _ in events]
    parent = [False] * len(events)
    stack = []
    for s, e, i in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack:
            j = stack[-1][2]
            parent[j] = True
            self_ns[j] -= e - s
        stack.append((s, e, i))
    return self_ns, parent


class _Spans:
    """The benchmark's spans inside the window, which follow one another on
    one host thread, looked up by time."""

    def __init__(self, spans):
        self.spans = sorted((s, e, n) for n, s, e in spans
                            if n != "bench.window")
        self.starts = [s for s, _, _ in self.spans]

    def at(self, t) -> str:
        i = bisect.bisect_right(self.starts, t) - 1
        if i >= 0 and self.spans[i][1] >= t:
            return self.spans[i][2]
        return "between bench spans"


def reduce(space) -> Reduced:
    """`space` is a `jax.profiler.ProfileData`."""
    spans = []
    for plane in space.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.name, ev.start_ns, ev.end_ns))
    windows = [s for s in spans if s[0] == "bench.window"]
    if not windows:
        raise ValueError("the trace holds no bench.window span")
    _, w0, w1 = max(windows, key=lambda s: s[2] - s[1])

    per_device = []
    op_seconds: Dict[str, float] = defaultdict(float)
    ops = []
    for plane in space.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        events, names = [], []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                s, e = max(ev.start_ns, w0), min(ev.end_ns, w1)
                if e > s:
                    events.append((s, e, len(events)))
                    names.append(ev.name)
        self_ns, parent = _self_times(events)
        for (s, e, i), own, enclosing in zip(events, self_ns, parent):
            op_seconds[short_name(names[i])] += own / 1e9
            if not enclosing:
                ops.append((names[i], s, e))
        per_device.append(_union([(s, e) for s, e, _ in events]))
    if not per_device:
        raise ValueError("the trace holds no device plane")
    busy = sum(sum(e - s for s, e in u) for u in per_device) / len(per_device)

    lookup = _Spans(spans)
    gaps = []
    t = w0
    for s, e in per_device[0]:
        if s > t:
            gaps.append((t, s, lookup.at((t + s) / 2)))
        t = max(t, e)
    if w1 > t:
        gaps.append((t, w1, lookup.at((t + w1) / 2)))
    return Reduced((w0, w1), busy / 1e9, dict(op_seconds), ops, gaps)


def load(trace_dir: str) -> Reduced:
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(find_xspace(trace_dir)))


def breakdown(red: Reduced, top: int = 10) -> dict:
    """The device ops that took the most time, and idle time by what the
    host was doing, each as [name, seconds]."""
    idle: Dict[str, float] = defaultdict(float)
    for s, e, label in red.gaps:
        idle[label] += (e - s) / 1e9
    ops = sorted(red.op_seconds.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}
