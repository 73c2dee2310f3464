"""Open-loop traffic from a mix file (`bench/traffic/<mix>.json`) and a seed.

A mix states the arrival process and rate, the shared prefixes (how many, how
long, Zipf popularity), the prompt length after the prefix and the output
length, each a distribution. The schedule is the mix's own and the same for
every seed: sizes are the distribution's quantiles at (i + 0.5) / n, prefix
counts follow the Zipf shares, gaps are exponential quantiles scaled so that
the n arrivals fill the window, and one fixed permutation (`SCHEDULE_SEED`)
orders each. The run's seed draws every token id, so runs of different seeds
do the same work at the same moments on different prompts (and, in
`bench/weights.py`, different weights). Near the knee the order of the gaps
decides the queue, so a per-seed order would make the tails swing from seed
to seed (see PERF.md).
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from statistics import NormalDist
from typing import List

import numpy as np

# ids 0 and 1 are the program's pad and default end-of-sequence tokens
FIRST_ID = 2
SCHEDULE_SEED = 20250428


@dataclasses.dataclass
class Arrival:
    due_s: float            # seconds after the window opens
    prompt: List[int]
    max_new_tokens: int
    prefix: int             # index of the shared prefix, -1 for none


def load(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def quantiles(spec: dict, n: int) -> np.ndarray:
    """n lengths at the (i + 0.5) / n quantiles of a length distribution."""
    if spec["dist"] == "fixed":
        return np.full(n, int(spec["value"]))
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    raw = np.round(spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(raw, spec["min"], spec["max"]).astype(int)


def length_range(spec: dict, scale: float = 1.0):
    """The least and the most length a distribution can draw, after
    `generate`'s scaling."""
    lo, hi = ((spec["value"], spec["value"]) if spec["dist"] == "fixed"
              else (spec["min"], spec["max"]))
    return tuple(max(1, int(round(x * scale))) for x in (lo, hi))


def zipf_counts(count: int, s: float, n: int) -> np.ndarray:
    """How many of n requests use each of `count` prefixes, by Zipf(s)
    shares, rounded by largest remainder."""
    share = 1.0 / np.arange(1, count + 1) ** s
    share = share / share.sum() * n
    out = np.floor(share).astype(int)
    for i in np.argsort(share - out)[::-1][:n - out.sum()]:
        out[i] += 1
    return out


def generate(mix: dict, seconds: float, seed: int, vocab: int,
             scale: float = 1.0) -> List[Arrival]:
    """The arrivals of one window. `scale` shrinks every length (CPU
    rehearsal only)."""
    n = max(1, int(round(mix["rate_per_s"] * seconds)))
    rng = np.random.default_rng(seed)
    order = np.random.default_rng(SCHEDULE_SEED)

    def shrink(a):
        return np.maximum(1, np.round(np.asarray(a) * scale)).astype(int)

    pre = mix["prefixes"]
    prefix_len = int(shrink(pre["length"])) if pre["count"] else 0
    prefixes = [rng.integers(FIRST_ID, vocab, prefix_len).tolist()
                for _ in range(pre["count"])]
    which = (np.repeat(np.arange(pre["count"]),
                       zipf_counts(pre["count"], pre["zipf_s"], n))
             if pre["count"] else np.full(n, -1))
    prompt_len = shrink(quantiles(mix["prompt_len"], n))
    # at least 2: the engine serves 2 tokens to a request asking for 1
    out_len = np.maximum(2, shrink(quantiles(mix["output_len"], n)))
    if mix["arrivals"] != "poisson":
        raise ValueError(f"unknown arrival process {mix['arrivals']!r}")
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    which, prompt_len, out_len, gaps = (order.permutation(a) for a in
                                        (which, prompt_len, out_len, gaps))
    due = (np.cumsum(gaps) - gaps) * seconds / gaps.sum()
    return [Arrival(float(due[i]),
                    (prefixes[which[i]] if which[i] >= 0 else [])
                    + rng.integers(FIRST_ID, vocab, prompt_len[i]).tolist(),
                    int(out_len[i]), int(which[i]))
            for i in range(n)]
