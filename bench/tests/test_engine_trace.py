"""The engine's spans in a trace (`bench/engine_trace.py`), on a small
recorded trace.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests

The trace is an XSpace in text form: the benchmark's spans on `/host:CPU`
with the engine's `engine.*` spans nested inside `bench.step`, the decode
step carrying its `kind` argument as the engine writes it, and three ops
on the device's "XLA Ops" line.
"""
import pytest

from bench import engine_trace, trace_reduce

# times in microseconds from the line's start; the window is 0-100
HOST = [("bench.window", 0, 100, None), ("bench.wait", 0, 10, None),
        ("bench.step", 10, 44, None),
        ("engine.step", 11, 43, "decode"),
        ("engine.inputs", 12, 14, None), ("engine.launch", 14, 16, None),
        ("engine.sample", 30, 32, None), ("engine.fetch", 32, 40, None),
        ("engine.emit", 40, 42, None),
        ("bench.submit", 44, 45, None),
        ("bench.step", 45, 100, None),
        ("engine.step", 46, 99, "prefill"),
        ("engine.admit", 46, 98, None), ("engine.launch", 47, 50, None),
        ("engine.fetch", 85, 95, None)]
DEVICE = [("%fusion.1 = bf16[8,3584]{1,0} fusion(%a)", 5, 11, None),
          ("%fusion.2 = bf16[8,3584]{1,0} fusion(%a)", 16, 34, None),
          ("%fusion.3 = bf16[1024,3584]{1,0} fusion(%a)", 51, 88, None)]


def xspace_text(host):
    def plane(pid, name, line, events):
        names = sorted({n for n, _, _, _ in events})
        ids = {n: i + 1 for i, n in enumerate(names)}
        kind_id = len(ids) + 1

        def stats(kind):
            return (f'stats {{ metadata_id: {kind_id} str_value: "{kind}" }}'
                    if kind else "")
        evs = "".join(
            f"events {{ metadata_id: {ids[n]} offset_ps: {s * 10**6} "
            f"duration_ps: {(e - s) * 10**6} {stats(k)} }}\n"
            for n, s, e, k in events)
        meta = "".join(
            f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}\n'
            for n, i in ids.items())
        meta += (f'stat_metadata {{ key: {kind_id} value {{ id: {kind_id} '
                 'name: "kind" } }\n')
        return (f'planes {{ id: {pid} name: "{name}" lines {{ id: 1 '
                f'name: "{line}" timestamp_ns: 0\n{evs}}}\n{meta}}}\n')
    return (plane(1, "/host:CPU", "python3", host)
            + plane(2, "/device:TPU:0", "XLA Ops", DEVICE))


def _load(host):
    from jax.profiler import ProfileData
    space = ProfileData.from_text_proto(xspace_text(host))
    return trace_reduce.reduce(space), engine_trace.spans(space)


@pytest.fixture(scope="module")
def traced():
    return _load(HOST)


def test_gaps_take_the_innermost_span(traced):
    red, spans = traced
    assert [n for n, _, _, _ in spans][:3] == [
        "engine.step", "engine.inputs", "engine.launch"]
    assert spans[0][3] == "decode" and spans[1][3] is None
    got = [((e - s) / 1e3, label) for s, e, label in
           engine_trace.label_gaps(red.gaps, spans)]
    assert got == [(5.0, "bench.wait"), (5.0, "engine.inputs"),
                   (17.0, "engine.step"), (12.0, "engine.fetch")]
    # the reduction's own labels stay the benchmark's spans
    assert [label for _, _, label in red.gaps] == [
        "bench.wait", "bench.step", "bench.step", "bench.step"]
    # every idle nanosecond keeps one label: they sum to the window less busy
    idle = sum(e - s for s, e, _ in engine_trace.label_gaps(red.gaps, spans))
    assert idle / 1e9 == pytest.approx(red.window_s - red.busy_s)
    assert red.busy_s == pytest.approx(61e-6)


def test_engine_readings(traced):
    red, spans = traced
    # 5 + 17 + 12 us of the 100 us window sit under an engine span; the
    # device idles 39 us in all
    assert engine_trace.engine_idle_share(red, spans) == pytest.approx(34.0)
    # the decode step (32 us) less its fetch (8 us); the prefill step is not
    # a decode step
    assert engine_trace.decode_host_ms(red, spans) == pytest.approx(0.024)


def test_no_engine_spans_no_reading():
    red, spans = _load([h for h in HOST if not h[0].startswith("engine.")])
    assert spans == []
    assert engine_trace.label_gaps(red.gaps, spans) == red.gaps
    assert engine_trace.engine_idle_share(red, spans) is None
    assert engine_trace.decode_host_ms(red, spans) is None
