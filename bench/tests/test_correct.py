"""The correctness check, driven through the harness at a size a CPU holds.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests

No chip: the harness's look for a TPU is skipped, the weights are drawn at
reduced widths (d 512, 4 layers, vocabulary 4096) and the program runs its
XLA path. Everything else is a run's: the open-loop window over the short
mix (16 requests/s for 3 s, so that every decode slot stays full), the
seeded sample, the reference and the checks. At this size, over seeds 1-5,
the program's widest gap read 0.0099-0.0411 (the same with the CPU's
dispatch synchronous or asynchronous) and the fp8 control's 0.137-0.297
(the int8 control's 0.046-0.079 does not separate here), so the test limit
is 0.06. Each fault planted in the timed path must turn `correct` false.
"""
import functools

import pytest

from bench import correct, harness

DIMS = {"L": 4, "d": 512, "f": 1536, "N": 8, "K": 2, "H": 64, "V": 4096}
LIMIT = {"max_logit_gap": 0.06}
SECONDS = 3.0


@pytest.fixture(autouse=True)
def small(monkeypatch):
    monkeypatch.setattr(harness.load_cell("qwen2-7b.short128-q8").arch,
                        "REHEARSE_DIMS", DIMS)


def serve(seed, plant=None):
    """One run of the short mix without the chip; `plant(engine)` breaks
    the timed path first. Returns (served, window, checked pairs)."""
    from repro.config import RuntimeConfig
    cell = harness.load_cell("qwen2-7b.short128-q8")
    cell.mix = dict(cell.mix, rate_per_s=16.0)
    served = harness.Served(cell, seed, rehearse=True)
    served.rcfg = RuntimeConfig(use_pallas=False)
    engine = served.engine()
    if plant:
        plant(engine)
    win = harness.run_window(engine, served.arrivals(SECONDS, seed), SECONDS)
    del engine
    pairs = correct.served_pairs(harness.sample_for_check(win, seed))
    served.free_program()
    return served, win, pairs


def verdict(served, win, pairs):
    gaps = correct.logit_gaps(served.cell.arch, served.w, served.cell.variant,
                              served.dims, pairs, **served.check_shape())
    return correct.passed(correct.checks(win, gaps, LIMIT)), gaps


def test_program_is_correct_and_control_is_not():
    served, win, pairs = serve(5)
    ok, gap = verdict(served, win, pairs)
    assert ok, gap
    ctrl = correct.control_gaps(served.cell.arch, served.w, served.cell.variant,
                                served.dims, pairs, "fp8",
                                **served.check_shape())
    assert ctrl["max_logit_gap"] > LIMIT["max_logit_gap"], ctrl


def _alter_tokens(engine):
    """A token altered where it is produced: every third one, plus one."""
    emit = engine._emit
    count = [0]

    def altered(req, slot, tok):
        count[0] += 1
        emit(req, slot, tok + 1 if count[0] % 3 == 0 else tok)
    engine._emit = altered


def _state_unchanged(engine):
    """A step that returns its state unchanged: admission leaves the KV
    pool as it was, so decode reads no prompt."""
    engine._scatter_cache_fn = lambda pool, *args: pool
    engine._scatter_kv_fn = lambda pool, *args: pool


def _half_batch(engine):
    """Half of the batch left out: decode rows in the second half get the
    first half's logits."""
    build = engine._decode_fn

    def decode_fn(variant=None):
        fn = build(variant)

        @functools.wraps(fn)
        def step(*args):
            logits, state = fn(*args)
            half = logits.shape[0] // 2
            return logits.at[half:].set(logits[:half]), state
        return step
    engine._decode_fn = decode_fn


@pytest.mark.parametrize("plant", [_alter_tokens, _state_unchanged,
                                   _half_batch],
                         ids=["token altered", "state unchanged",
                              "half batch left out"])
def test_planted_fault_is_not_correct(plant):
    ok, gap = verdict(*serve(6, plant))
    assert not ok, gap
