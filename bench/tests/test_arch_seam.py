"""The architecture plug-in seam, at a size a CPU holds.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests/test_arch_seam.py

A configuration names its plug-in by "bench_arch", and `harness.load_arch`
loads that module by its path. Here `stub_arch.py`, beside this file (no QKV
bias, a GeGLU MLP, weight names of its own), is loaded as a new
architecture's module would be from `bench/archs/`, and driven through a
whole run without the chip: `Served` draws its weights and builds the
program from it, `warm_up`, the open-loop window over the short mix at
16 requests/s (every decode slot full), its reference's gaps through
`correct.logit_gaps` against `test_correct.py`'s limit, and `mfu` from its
operation count. A fault planted in the stub's served model must turn
`correct` false. The Qwen2 plug-in must draw the same weights and count the
same operations as the code it was moved from.
"""
import dataclasses
import hashlib
import json
from pathlib import Path

import pytest

from bench import correct, harness
from bench import run as run_mod

HERE = Path(__file__).resolve().parent
LIMIT = {"max_logit_gap": 0.06}
SECONDS = 3.0


def stub_cell():
    qwen = harness.load_cell("qwen2-7b.short128-q8")
    cfg = {"arch": "stub-geglu", "bench_arch": "stub_arch", "n_layer": 2,
           "d_model": 256, "d_ff": 512, "vocab": 1024, "heads": 4,
           "kv_heads": 2, "head_dim": 64, "eps": 1e-6, "theta": 10000.0,
           "variants": ["q8"], "engine": qwen.cfg["engine"]}
    return harness.Cell("stub_arch.short128-q8", 1, cfg,
                        dict(qwen.mix, rate_per_s=16.0), qwen.spec)


@pytest.fixture
def stub(monkeypatch):
    """The stub plug-in, found where `bench/archs/` modules are."""
    monkeypatch.setattr(harness, "ARCHS", HERE)
    cell = stub_cell()
    return cell, cell.arch


def serve(cell, seed):
    from repro.config import RuntimeConfig
    served = harness.Served(cell, seed, rehearse=True)
    served.rcfg = RuntimeConfig(use_pallas=False)
    harness.warm_up(served, seed)
    win = harness.run_window(served.engine(), served.arrivals(SECONDS, seed),
                             SECONDS)
    pairs = correct.served_pairs(harness.sample_for_check(win, seed))
    served.free_program()
    gaps = correct.logit_gaps(cell.arch, served.w, cell.variant,
                              served.dims, pairs, **served.check_shape())
    return served, win, gaps


def test_stub_architecture_runs_and_is_correct(stub, monkeypatch):
    cell, arch = stub
    served, win, gaps = serve(cell, 7)
    assert served.model_cfg.act_fn == "gelu"
    assert not served.model_cfg.qkv_bias
    assert correct.passed(correct.checks(win, gaps, LIMIT)), gaps
    peaks = json.loads((harness.BENCH / "peaks.json").read_text())
    run = run_mod.Run(cell, served.dims, win, 0.0, peaks["TPU v5 lite"])
    mfu = run_mod.reader("mfu").read(run)
    assert mfu is not None and mfu > 0
    # the share is the plug-in's count over the steps' time
    for name in ("body_flops", "head_flops", "mixer_flops"):
        fn = getattr(arch, name)
        monkeypatch.setattr(arch, name, lambda *a, fn=fn: 2 * fn(*a))
    assert run_mod.reader("mfu").read(run) == pytest.approx(2 * mfu)


def _swap_gate_up(arch, monkeypatch):
    """The stub's served tree wires the MLP's gate where its up goes."""
    wrap = arch.program_params

    def swapped(w, fmt, spec):
        p = wrap(w, fmt, spec)
        mlp = p["layers"]["mlp"]
        mlp["wg"], mlp["wu"] = mlp["wu"], mlp["wg"]
        return p
    monkeypatch.setattr(arch, "program_params", swapped)


def _swiglu_served(arch, monkeypatch):
    """The stub's program serves SiLU where its configuration says GELU."""
    wrap = arch.model_config
    monkeypatch.setattr(arch, "model_config", lambda cfg, dims: dataclasses
                        .replace(wrap(cfg, dims), act_fn="silu"))


@pytest.mark.parametrize("plant", [_swap_gate_up, _swiglu_served],
                         ids=["gate and up swapped", "swiglu served"])
def test_planted_fault_in_stub_is_not_correct(stub, plant, monkeypatch):
    cell, arch = stub
    plant(arch, monkeypatch)
    _, win, gaps = serve(cell, 8)
    assert not correct.passed(correct.checks(win, gaps, LIMIT)), gaps


def test_configuration_without_plug_in_is_refused():
    with pytest.raises(KeyError, match="bench_arch"):
        harness.load_arch({"arch": "carboncall-qwen2-7b"})
    with pytest.raises(FileNotFoundError, match="nonesuch"):
        harness.load_arch({"bench_arch": "nonesuch"})


def _qwen2():
    cfg = harness.load_cell("qwen2-7b.short128-q8").cfg
    arch = harness.load_arch(cfg)
    return arch, arch.dims_of(cfg)


def test_qwen2_operation_count_is_unchanged():
    """The counts `bench/flops.py` gave `mfu` before the seam, at
    qwen2-7b's published widths."""
    arch, dims = _qwen2()
    assert arch.body_flops(dims) == 13050576896
    assert arch.head_flops(dims) == 1089994752
    assert arch.mixer_flops(dims, 1) == 401408
    assert arch.mixer_flops(dims, 128.5) == 51580928.0


def test_qwen2_draw_is_unchanged():
    """Every array the Qwen2 plug-in draws at rehearsal widths, seed
    3000000101, bit for bit as `bench/weights.py` drew it before the seam."""
    import jax
    import numpy as np

    from bench import weights
    arch, dims = _qwen2()
    dims.update(arch.REHEARSE_DIMS)
    w = arch.draw(dims, ("q8", "q4"), weights.seed_key(3000000101))
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(w)[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == ("f7695abdd478098f56beb81aca98d606"
                             "e04783af9235421125f67a53b6bac421")
