"""Whether what the timed path served is correct.

Once the window has closed and the program's state is freed, the plain
reference of the configuration's architecture (`logits_at` of its plug-in,
`bench/archs/<name>.py`, built on `bench/reference.py`) reads each sampled
request's prompt and served tokens in one teacher-forced pass. At every
served position it measures how far the served token's reference logit lies
below the reference's best logit there. The numbers compared are the widest
such gap over the sample (`max_logit_gap`) and their mean (`mean_logit_gap`), each
where the cell's `bench/limits/<cell>.json` gives it a limit: greedy
serving at temperature 0 should pick the reference's best token, or one
within bf16 rounding of it.

The control (`control_gaps`) is the reference in the program's place at the
next precision down: the same pass with every matrix product's input
rounded to float8 e4m3 (`act="fp8"`; int8 reads lower at small widths,
see PERF.md). It reads the gap of the token that the lower precision puts
first. The
benchmark's runs never compute it; `bench/calibrate.py` does, on the chip,
and `bench/tests/` does at a test size.

Besides the gap: every request due in the window finished, and each served
exactly the tokens it asked for (the traffic disables end-of-sequence).
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from bench import reference


def served_pairs(records) -> List[tuple]:
    """(prompt, served tokens) of each record, as the engine holds them."""
    return [(r.prompt, r.output) for r in records]


def _numbers(gaps) -> Dict[str, float]:
    return {"max_logit_gap": float(gaps.max()),
            "mean_logit_gap": float(gaps.mean())}


def logit_gaps(arch, w, fmt, dims, pairs, **shape) -> Dict[str, float]:
    """The widest and the mean gap of the served tokens. `arch`: the
    architecture plug-in; `shape`: `seq_len` and `positions` for its
    `logits_at`."""
    logits, tokens = arch.logits_at(w, fmt, dims, pairs, **shape)
    return _numbers(reference.gaps(logits, tokens))


def control_gaps(arch, w, fmt, dims, pairs, act: str = "fp8",
                 **shape) -> Dict[str, float]:
    """The same numbers for the tokens a lower-precision pass (`act`
    activations) puts first, at the same positions of the same prompts and
    served tokens."""
    ref, _ = arch.logits_at(w, fmt, dims, pairs, **shape)
    low, _ = arch.logits_at(w, fmt, dims, pairs, act=act, **shape)
    return _numbers(reference.gaps(ref, low.argmax(axis=-1)))


def checks(win, gaps: Dict[str, float],
           limits: Dict[str, float]) -> Dict[str, dict]:
    """Each compared number beside its limit; all must be at or under it.
    A gap is compared where the cell's limits file gives it a limit."""
    unfinished = sum(1 for r in win.records if not r.done)
    short = sum(1 for r in win.records
                if r.done and len(r.output) != r.asked)
    out = {"unfinished": {"value": unfinished, "limit": 0},
           "short_answers": {"value": short, "limit": 0}}
    for name, value in gaps.items():
        if name in limits:
            out[name] = {"value": value, "limit": limits[name]}
    return out


def passed(result: Dict[str, dict]) -> bool:
    return all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in result.values())
