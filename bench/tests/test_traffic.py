"""Traffic generation and the warm-up's admission classes, on the CPU.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests

The mixes here draw prompts of many lengths, as the function-calling and
short-turn mixes that wait for the engine's left-padding fault to be mended
(PERF.md, Open questions) will: the warm-up has to leave their window
nothing to compile.
"""
import itertools
import random
from collections import Counter

import pytest

from bench import harness, traffic

TOOLCALL = {
    "variant": "q8", "rate_per_s": 1.0, "arrivals": "poisson",
    "prefixes": {"count": 8, "length": 512, "zipf_s": 1.1},
    "prompt_len": {"dist": "lognormal", "median": 48, "sigma": 0.9,
                   "min": 16, "max": 256},
    "output_len": {"dist": "lognormal", "median": 32, "sigma": 0.7,
                   "min": 8, "max": 128},
    "temperature": 0.0}
SHORT = {
    "variant": "q8", "rate_per_s": 1.0, "arrivals": "poisson",
    "prefixes": {"count": 0, "length": 0, "zipf_s": 0.0},
    "prompt_len": {"dist": "lognormal", "median": 128, "sigma": 0.6,
                   "min": 32, "max": 256},
    "output_len": {"dist": "lognormal", "median": 128, "sigma": 0.6,
                   "min": 32, "max": 256},
    "temperature": 0.0}
MIX = TOOLCALL
# reduced widths for the warm-up's run on the CPU
SMALL = {"L": 2, "d": 128, "f": 256, "N": 4, "K": 2, "H": 32, "V": 512}


def test_every_seed_does_the_same_work():
    a = traffic.generate(MIX, 30, 1, 152064)
    b = traffic.generate(MIX, 30, 2 ** 31 + 7, 152064)
    assert len(a) == len(b) == round(MIX["rate_per_s"] * 30)
    for key in (lambda r: len(r.prompt), lambda r: r.max_new_tokens,
                lambda r: r.prefix, lambda r: r.due_s):
        assert list(map(key, a)) == list(map(key, b))
    assert all(0 <= r.due_s < 30 for r in a)
    assert [r.prompt for r in a] != [r.prompt for r in b]
    # the sizes are the distribution's quantiles
    out = sorted(r.max_new_tokens for r in a)
    assert out[0] >= MIX["output_len"]["min"]
    assert out[-1] <= MIX["output_len"]["max"]
    assert Counter(r.prefix for r in a) == Counter(
        dict(enumerate(traffic.zipf_counts(8, 1.1, len(a)))))


def test_same_seed_same_traffic():
    a = traffic.generate(MIX, 20, 5, 152064)
    b = traffic.generate(MIX, 20, 5, 152064)
    assert [(r.due_s, r.prompt, r.max_new_tokens) for r in a] == \
        [(r.due_s, r.prompt, r.max_new_tokens) for r in b]


def test_zipf_counts_sum_and_order():
    c = traffic.zipf_counts(8, 1.1, 33)
    assert c.sum() == 33 and list(c) == sorted(c, reverse=True)


@pytest.mark.parametrize("b,hit", [(768, 512), (96, 64)])
def test_admission_classes_cover_every_program(b, hit):
    """Every (suffix width, gathered blocks) and (suffix width, scatter
    length) an admission of up to 8 rows can produce, drawn at random,
    is among the warm-up's."""
    bs, B, max_seq = 16, 8, 1024
    big = 1 << 62
    pow2 = harness._pow2

    def programs(rows):
        if not any(rows):
            return {("cold", pow2(len(rows) * b, big))}
        s = pow2(b - min(rows), b)
        return {("prefix", s, pow2(-(-max(rows) // bs), max_seq // bs)),
                ("scatter", s, pow2(sum(b - c for c in rows), big))}

    warmed = set(itertools.chain.from_iterable(
        programs(r) for r in harness.admission_classes(b, hit, bs, B, max_seq)))
    rng = random.Random(0)
    grid = [0] + list(range(bs, hit + 1, bs))
    for _ in range(20000):
        rows = [rng.choice(grid) for _ in range(rng.randint(1, B))]
        assert programs(rows) <= warmed, rows


@pytest.mark.parametrize("mix", [TOOLCALL, SHORT], ids=["toolcall", "short"])
def test_warm_up_leaves_nothing_to_compile(mix, monkeypatch):
    """A mix of many prompt lengths, at reduced widths and lengths, on the
    program's XLA path: after the warm-up, a window that keeps every decode
    slot full and its drain compile nothing."""
    from repro.config import RuntimeConfig
    cell = harness.load_cell("qwen2-7b.short128-q8")
    monkeypatch.setattr(cell.arch, "REHEARSE_DIMS", SMALL)
    cell.mix = dict(mix, rate_per_s=16.0)
    served = harness.Served(cell, 3, rehearse=True)
    served.rcfg = RuntimeConfig(use_pallas=False)
    compiles = harness.CompileCounter()
    harness.warm_up(served, 3)
    before = compiles.count
    win = harness.run_window(served.engine(), served.arrivals(3.0, 3), 3.0,
                             compiles=compiles)
    assert len({r.prompt_len for r in win.records}) > 5
    assert all(r.done for r in win.records)
    assert compiles.count - before == 0
