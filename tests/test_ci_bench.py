"""CI bench pipeline: metric extraction from the JSON artifacts and the
benchmark-regression gate (fails on >20% TPS drop / carbon rise)."""
import json
import sys

import pytest

from benchmarks.ci_compare import compare, main as compare_main
from benchmarks.ci_metrics import collect, HIGHER, INFO, LOWER
from benchmarks.ci_summary import render


def _write_bench(dirpath, *, tps=70.0, carbon=0.0028, day_tps=12.0):
    dirpath.mkdir(parents=True, exist_ok=True)
    (dirpath / "fleet_engine.json").write_text(json.dumps({
        "occupancy": {"4": {"decode_tps": tps,
                            "carbon_g_per_query": carbon,
                            "peak_active": 4}},
        "fleet": {"queries": 10, "carbon_g_per_query": carbon, "pods": {}},
    }))
    (dirpath / "chunked_prefill.json").write_text(json.dumps({
        "chunked": {"decode_tps": tps, "chunk_steps": 25,
                    "stall_time_s": 0.4},
        "acceptance": {"interactive_p95_s": 1.9, "p95_speedup": 1.5,
                       "pass": True},
    }))
    (dirpath / "engine_week.json").write_text(json.dumps({
        "decode_tps": {"1": 17.0, "4": tps},
        "day": {"avg_tps": day_tps, "avg_carbon_g": carbon, "queries": 100},
        # versioned EngineStats wire payload (schema_version travels inside)
        "engine_stats": {"schema_version": 1, "admitted": 100,
                         "preemptions": 2, "expired": 1,
                         "prefix_cache": {"hits": 90, "misses": 10}},
    }))
    (dirpath / "fleet_workers.json").write_text(json.dumps({
        "workers": {"n_workers": 4, "agg_decode_tps": 2 * tps,
                    "carbon_g_per_query": carbon},
        "acceptance": {"wall_speedup": 1.6, "speedup_gate_skipped": True,
                       "pass": True},
    }))
    (dirpath / "spec_decode.json").write_text(json.dumps({
        "acceptance": {"decode_tps": 1.7 * tps,
                       "carbon_mg_per_query": 1000 * carbon * 0.9,
                       "decode_tps_ratio_vs_q8": 1.7,
                       "accept_rate": 0.79, "token_parity": True,
                       "pass": True},
    }))


def test_collect_extracts_tagged_metrics(tmp_path):
    _write_bench(tmp_path)
    m = collect(str(tmp_path))
    assert m["fleet_engine/decode_tps@4"].value == 70.0
    assert m["fleet_engine/decode_tps@4"].direction == HIGHER
    assert m["fleet_engine/carbon_g_per_query@4"].direction == LOWER
    assert m["engine_week/prefix_hit_rate"].value == pytest.approx(0.9)
    assert m["engine_week/sched_preemptions"].value == 2
    # chunked-prefill suite: p95 gates as a cost, chunk counters are info
    assert m["chunked_prefill/interactive_p95_s"].direction == LOWER
    assert m["chunked_prefill/decode_tps"].direction == HIGHER
    assert m["chunked_prefill/chunk_steps"].direction == INFO
    assert m["chunked_prefill/acceptance_pass"].value == 1.0
    # fleet_workers suite: virtual TPS + carbon gate, wall speedup is info
    assert m["fleet_workers/agg_decode_tps"].direction == HIGHER
    assert m["fleet_workers/carbon_g_per_query"].direction == LOWER
    assert m["fleet_workers/wall_speedup"].direction == INFO
    assert m["fleet_workers/speedup_gate_skipped"].value == 1.0
    assert m["fleet_workers/speedup_gate_skipped"].direction == INFO
    assert m["fleet_workers/acceptance_pass"].value == 1.0
    # spec_decode suite: TPS + carbon gate vs plain Q8, rest is info
    assert m["spec_decode/decode_tps"].direction == HIGHER
    assert m["spec_decode/carbon_mg_per_query"].direction == LOWER
    assert m["spec_decode/decode_tps_ratio_vs_q8"].direction == HIGHER
    assert m["spec_decode/accept_rate"].direction == INFO
    assert m["spec_decode/token_parity"].value == 1.0
    assert m["spec_decode/acceptance_pass"].value == 1.0
    # missing dir / empty dir -> empty mapping, never raises
    assert collect(str(tmp_path / "nope")) == {}


def test_gate_trips_on_tps_drop(tmp_path):
    """The acceptance scenario: a synthetic >20% decode-TPS drop must fail
    the comparison with an annotation-ready old-vs-new record."""
    _write_bench(tmp_path / "prev", tps=70.0)
    _write_bench(tmp_path / "new", tps=50.0)        # -28.6%
    regs, rows = compare(collect(str(tmp_path / "prev")),
                         collect(str(tmp_path / "new")))
    names = {r.name for r in regs}
    assert "fleet_engine/decode_tps@4" in names
    assert "engine_week/decode_tps@4" in names
    r = next(r for r in regs if r.name == "fleet_engine/decode_tps@4")
    assert r.old == 70.0 and r.new == 50.0
    assert "dropped" in r.reason
    assert any("->" in row for row in rows)


def test_gate_allows_small_drift(tmp_path):
    _write_bench(tmp_path / "prev", tps=70.0, carbon=0.0028)
    _write_bench(tmp_path / "new", tps=63.5, carbon=0.0032)   # <20% both
    regs, _ = compare(collect(str(tmp_path / "prev")),
                      collect(str(tmp_path / "new")))
    assert regs == []


def test_gate_trips_on_carbon_rise(tmp_path):
    _write_bench(tmp_path / "prev", carbon=0.0028)
    _write_bench(tmp_path / "new", carbon=0.0040)   # +42.9%
    regs, _ = compare(collect(str(tmp_path / "prev")),
                      collect(str(tmp_path / "new")))
    assert any(r.name == "fleet_engine/carbon_g_per_query@4" for r in regs)
    assert all("rose" in r.reason for r in regs)


def test_info_metrics_never_gate(tmp_path):
    """Scheduler counters may swing wildly without failing the build."""
    _write_bench(tmp_path / "prev")
    _write_bench(tmp_path / "new")
    new = collect(str(tmp_path / "new"))
    prev = collect(str(tmp_path / "prev"))
    # simulate a 10x preemption jump (info-tagged)
    import dataclasses
    new["engine_week/sched_preemptions"] = dataclasses.replace(
        new["engine_week/sched_preemptions"], value=20.0)
    regs, _ = compare(prev, new)
    assert regs == []


def test_main_exit_codes(tmp_path, monkeypatch, capsys):
    """First run (no baseline) passes trivially; a regression exits 1 with
    a ::error:: annotation and a step-summary table."""
    _write_bench(tmp_path / "new", tps=50.0)
    monkeypatch.setattr(sys, "argv", [
        "ci_compare", str(tmp_path / "missing"), str(tmp_path / "new")])
    assert compare_main() == 0
    assert "passes trivially" in capsys.readouterr().out

    _write_bench(tmp_path / "prev", tps=70.0)
    summary = tmp_path / "summary.md"
    monkeypatch.setattr(sys, "argv", [
        "ci_compare", str(tmp_path / "prev"), str(tmp_path / "new"),
        "--summary", str(summary)])
    assert compare_main() == 1
    out = capsys.readouterr().out
    assert "::error title=benchmark regression::" in out
    assert "70 -> 50" in out
    md = summary.read_text()
    assert "Benchmark regression gate" in md and "❌" in md

    # identical artifacts -> clean pass
    monkeypatch.setattr(sys, "argv", [
        "ci_compare", str(tmp_path / "prev"), str(tmp_path / "prev")])
    assert compare_main() == 0


def test_step_summary_renders_table(tmp_path):
    _write_bench(tmp_path)
    md = render(str(tmp_path))
    assert "| suite | metric | value |" in md
    assert "decode_tps@4" in md and "prefix_hit_rate" in md
    assert "no benchmark JSON" in render(str(tmp_path / "empty"))


def test_kernels_suite_runs_on_cpu():
    """`benchmarks/run.py kernels` drives every kernel through the
    interpreter on the CPU and emits one row per kernel case."""
    from benchmarks import common, kernels_bench
    start = len(common.ROWS)
    kernels_bench.run()
    names = [r[0] for r in common.ROWS[start:]]
    for kernel in ("quant_matmul/q8", "quant_matmul/q4",
                   "flash_attention/causal", "flash_attention/window",
                   "ssd/chunked", "paged_attention/bf16",
                   "paged_attention/int8", "topk_sim/"):
        assert any(n.startswith(f"kernels/{kernel}") for n in names), kernel
    assert len(names) == 8
