"""The engine step's spans and per-step counters (serving/tracing.py).

Every `step_log` entry carries `host` (seconds per phase) and `compiles`;
under a `VirtualClock` the phases read 0 and the token streams are the
ones the engine served before it was traced. Under a real clock a step's
phases sum to at most its `dt`. Under `jax.profiler` the host plane holds
one `engine.step` span per entry, carrying its kind and index, with every
phase span nested inside it.
"""
import glob
import os
import time

import jax
import pytest

from repro.config import ModelConfig, RuntimeConfig
from repro.models import get_model
from repro.quant import quantize_tree
from repro.serving import (Request, ServingEngine, SpecDecodeConfig,
                           VirtualClock)
from repro.serving.tracing import PHASES, SPANS, STEP_SPAN
from repro.sharding.param import init_params

CFG = ModelConfig(name="trace-tiny", family="transformer", num_layers=2,
                  d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                  vocab_size=256)
RCFG = RuntimeConfig()
PREFIX = list(range(11, 31))
PROMPTS = [PREFIX + [5, 6], [40, 41, 42], PREFIX + [7], PREFIX + [8, 9, 10],
           list(range(50, 90))]

# one path of step() each: cold and suffix (prefix-hit) admission, chunked
# prefill, preemption and resume, speculative draft/verify; all decode
SCENARIOS = {
    "paged": dict(kv_layout="paged"),
    "dense": dict(kv_layout="dense"),
    "paged_chunked": dict(kv_layout="paged", prefill_chunk=16),
    "dense_chunked": dict(kv_layout="dense", prefill_chunk=16),
    "resume": dict(kv_layout="paged", num_blocks=6, block_size=16),
    "spec": dict(kv_layout="paged",
                 spec_decode=SpecDecodeConfig(draft_variant="q4", k=2)),
}

# greedy streams by rid, served by the engine before tracing was added
PLAIN = [[162, 19, 111, 171, 231, 104], [104, 190, 88, 94, 95, 104],
         [124, 37, 73, 82, 88, 94], [143, 190, 225, 88, 94, 80],
         [230, 232, 37, 73, 249, 207]]
STREAMS = {
    "paged": PLAIN, "dense": PLAIN, "paged_chunked": PLAIN,
    "dense_chunked": PLAIN,
    "resume": [[71, 118, 126, 183, 73, 99, 94, 24, 193, 180, 99, 94, 24,
                193, 124, 124, 124, 124, 124, 124], [232, 111, 38, 184]],
    "spec": [[162, 19, 111, 171, 231, 135]] + PLAIN[1:],
}


@pytest.fixture(scope="module")
def variants():
    spec = get_model(CFG).param_spec()
    params = init_params(spec, jax.random.PRNGKey(0))
    return {"bf16": params, "q8": quantize_tree(params, spec, "q8"),
            "q4": quantize_tree(params, spec, "q4")}


def _serve(name, variants, clock):
    kw = dict(max_batch=2, max_seq=64, block_size=8)
    kw.update(SCENARIOS[name])
    if isinstance(clock, VirtualClock):
        kw["step_cost_fn"] = lambda kind, tok, act: 0.01
    eng = ServingEngine(CFG, variants["q8" if name == "spec" else "bf16"],
                        RCFG, clock=clock, **kw)
    if name == "spec":
        eng.variant_name = "q8"
        eng.set_draft_params(variants["q4"], "q4")
    if name == "resume":
        # a low-priority stream mid-decode, then a high-priority admission
        # into a pool too small for both
        eng.submit(Request(rid=0, prompt=[3] * 20, max_new_tokens=20,
                           eos_id=-1))
        for _ in range(6):
            eng.step()
        eng.submit(Request(rid=1, prompt=[9] * 20, max_new_tokens=4,
                           eos_id=-1, priority=10))
    else:
        for rid, p in enumerate(PROMPTS):
            eng.submit(Request(rid=rid, prompt=p, max_new_tokens=6,
                               eos_id=-1))
    done = sorted(eng.run_until_drained(), key=lambda r: r.rid)
    return eng, [r.output for r in done]


@pytest.mark.parametrize("name", list(SCENARIOS))
def test_step_records_under_virtual_clock(variants, name):
    eng, streams = _serve(name, variants, VirtualClock())
    assert streams == STREAMS[name]
    kinds = {rec["kind"] for rec in eng.step_log}
    want = {"resume": {"prefill", "decode"},
            "spec": {"prefill", "decode", "spec_verify"}}.get(
                name, {"prefill", "decode"})
    assert want <= kinds
    if name.endswith("chunked"):
        assert "prefill_chunk" in kinds
    if name == "resume":
        assert eng.scheduler_stats()["preemptions"] >= 1
    for rec in eng.step_log:
        assert set(rec["host"]) == set(PHASES)
        assert all(v == 0.0 for v in rec["host"].values())
        assert isinstance(rec["compiles"], int) and rec["compiles"] >= 0
        assert "tps" not in rec


@pytest.mark.parametrize("name", ["paged", "dense", "spec"])
def test_phases_sum_within_step(variants, name):
    eng, _ = _serve(name, variants, time.monotonic)
    for rec in eng.step_log:
        assert all(v >= 0.0 for v in rec["host"].values())
        assert sum(rec["host"].values()) <= rec["dt"]
    assert any(rec["host"]["launch"] > 0.0 for rec in eng.step_log)


def test_compiles_counted_on_a_fresh_bucket(variants):
    """Admission into a bucket nothing has compiled for compiles; a repeat
    of the same shapes compiles nothing. The persistent cache is off, so a
    program another run cached cannot stand in for a compile."""
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        # a max_seq no other test of this model uses: its programs are new
        eng = ServingEngine(CFG, variants["bf16"], RCFG, max_batch=2,
                            max_seq=40, prompt_buckets=(8, 16),
                            kv_layout="paged", block_size=8)
        rows = []
        for rid, prompt in enumerate([[5, 6, 7], [8, 9, 10], list(range(
                20, 32))]):
            eng.submit(Request(rid=rid, prompt=prompt, max_new_tokens=3,
                               eos_id=-1))
            start = len(eng.step_log)
            eng.run_until_drained()
            rows.append(eng.step_log[start:])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
    first, repeat, wider = rows
    assert first[0]["kind"] == "prefill" and first[0]["compiles"] >= 1
    assert first[1]["kind"] == "decode" and first[1]["compiles"] >= 1
    assert [r["compiles"] for r in repeat] == [0] * len(repeat)
    assert wider[0]["kind"] == "prefill" and wider[0]["compiles"] >= 1
    assert [r["compiles"] for r in wider[1:]] == [0] * (len(wider) - 1)


def _host_spans(trace_dir):
    """(name, start, end, args) of each `engine.*` event on the host plane,
    and the thread line it sits on."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("engine."):
                    out.append((ev.name, ev.start_ns, ev.end_ns,
                                dict(ev.stats), line.name))
    return out


@pytest.mark.parametrize("name", ["paged", "dense", "spec"])
def test_spans_nest_in_their_step(variants, name, tmp_path):
    _serve(name, variants, VirtualClock())          # compiles outside
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng, streams = _serve(name, variants, VirtualClock())
    finally:
        jax.profiler.stop_trace()
    assert streams == STREAMS[name]
    spans = _host_spans(str(tmp_path))
    steps = sorted((s for s in spans if s[0] == STEP_SPAN),
                   key=lambda s: s[1])
    assert len(steps) == len(eng.step_log)
    for i, (_, _, _, args, _) in enumerate(steps):
        assert args["index"] == i
        assert args["kind"] == eng.step_log[i]["kind"]
    children = [s for s in spans if s[0] != STEP_SPAN]
    for cname, s, e, _, line in children:
        assert any(line == sl and ss <= s and e <= se
                   for _, ss, se, _, sl in steps), cname
    names = {s[0] for s in children}
    want = set(SPANS.values())
    if name == "dense":
        want.discard(SPANS["blocks"])             # no block tables
    assert names == want
