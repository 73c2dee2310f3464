"""The trace reduction and the trace-read metrics on a small recorded trace.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests

The trace is an XSpace in text form with the planes, lines and op names a
TPU v5e trace of the served path has (`/host:CPU` with the benchmark's
spans; `/device:TPU:0` with an "XLA Ops" line whose events are named by
their HLO text and where a `while` encloses its body's ops).
"""
import pytest

from bench import flops, trace_reduce
from bench.metrics import device_idle_share, q8_matmul_roofline

Q8 = ("%quant_matmul.4 = bf16[8,3584]{1,0} custom-call(bf16[8,3584]{1,0} %a, "
      "s8[3584,3584]{1,0} %b, f32[1,3584]{1,0} %c), custom_call_target=x")
Q4 = ("%quant_matmul.9 = bf16[2048,512]{1,0} custom-call(bf16[2048,5120]{1,0} "
      "%a, u8[2560,512]{1,0} %b, f32[40,512]{1,0} %c, f32[40,512]{1,0} %d)")

# times in microseconds from the line's start; the window is 0-100
HOST = [("bench.window", 0, 100), ("bench.wait", 0, 10),
        ("bench.step", 10, 40), ("bench.submit", 40, 41),
        ("bench.step", 41, 100)]
DEVICE = [("%while.2 = (s32[]) while(%t), body=%b", 12, 32),
          (Q8, 14, 20), (Q8, 22, 30), (Q4, 50, 90)]


def xspace_text():
    def plane(pid, name, line, events):
        names = sorted({n for n, _, _ in events})
        ids = {n: i + 1 for i, n in enumerate(names)}
        evs = "".join(
            f"events {{ metadata_id: {ids[n]} offset_ps: {s * 10**6} "
            f"duration_ps: {(e - s) * 10**6} }}\n" for n, s, e in events)
        meta = "".join(
            f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}\n'
            for n, i in ids.items())
        return (f'planes {{ id: {pid} name: "{name}" lines {{ id: 1 '
                f'name: "{line}" timestamp_ns: 0\n{evs}}}\n{meta}}}\n')
    return (plane(1, "/host:CPU", "python3", HOST)
            + plane(2, "/device:TPU:0", "XLA Ops", DEVICE))


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData
    return trace_reduce.reduce(ProfileData.from_text_proto(xspace_text()))


def test_window_busy_and_self_times(reduced):
    assert reduced.window_s == pytest.approx(100e-6)
    # union: the while (12-32, enclosing both Q8 calls) and the Q4 (50-90)
    assert reduced.busy_s == pytest.approx(60e-6)
    ops = reduced.op_seconds
    assert ops["while s32[]"] == pytest.approx(6e-6)       # 20 - 6 - 8
    assert ops["quant_matmul bf16[8,3584]"] == pytest.approx(14e-6)
    assert ops["quant_matmul bf16[2048,512]"] == pytest.approx(40e-6)
    # the while encloses others, so it is not a leaf op
    assert sorted(n.split(" =")[0] for n, _, _ in reduced.ops) == [
        "%quant_matmul.4", "%quant_matmul.4", "%quant_matmul.9"]


def test_gaps_are_labelled_by_host_span(reduced):
    got = [((e - s) / 1e3, label) for s, e, label in reduced.gaps]
    # each gap takes the span that covers its midpoint
    assert got == [(12.0, "bench.wait"), (18.0, "bench.step"),
                   (10.0, "bench.step")]
    bd = trace_reduce.breakdown(reduced)
    assert bd["idle_gaps"] == [["bench.step", pytest.approx(28e-6)],
                               ["bench.wait", pytest.approx(12e-6)]]
    assert bd["device_ops"][0] == ["quant_matmul bf16[2048,512]",
                                   pytest.approx(40e-6)]


class _Run:
    def __init__(self, trace):
        self.trace = trace
        self.peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_trace_metrics(reduced):
    run = _Run(reduced)
    assert device_idle_share.read(run) == pytest.approx(40.0)
    f, b = flops.q8_matmul(8, 3584, 3584)
    least = max(f / 197e12, b / 819e9)
    # the Q4 call (packed u8 codes) is not the Q8 kernel's
    assert q8_matmul_roofline.read(run) == pytest.approx(
        100 * 2 * least / 14e-6)


def test_no_calls_no_reading(reduced):
    run = _Run(reduced)
    run.trace = None
    assert q8_matmul_roofline.read(run) is None
    assert device_idle_share.read(run) is None
