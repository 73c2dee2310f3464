"""Sharded multi-host fleet scale-out benchmark.

Runs a FleetSpec-driven heterogeneous fleet (16+ pods across four grid
regions, mixed hardware profiles including a data-parallel sharded engine
over 4 forced host devices) through `run_fleet(backend="engine")` with
hierarchical region->pod routing, and measures

  * aggregate decode TPS vs pod count — the sum of per-pod decode
    throughput (pods run in parallel on the shared fleet clock), expected
    to scale near-linearly 4 -> 16 pods under saturating tiered traffic;
  * carbon per query — batch tiers shed to the clean region, so the fleet
    figure must come in at or below the qos_fleet PR 4 pressure figure
    (2.73 mg/query at CI 400);
  * the sharded profile's per-pod decode TPS vs the unsharded edge profile
    (a dp4 pod decodes 4 rows at near 1-row step latency).

Needs 8 forced host devices for the sharded profile; when imported into a
process that already initialized jax with fewer (the CI `run.py --json-dir`
path), `json_summary` re-executes itself in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.

    PYTHONPATH=src:. python benchmarks/fleet_scale.py [--json out.json]
"""
from __future__ import annotations

import os

if __name__ == "__main__":
    # forced host devices must be set before jax init (dryrun.py pattern),
    # and any inherited force-device flag must be stripped — XLA takes the
    # LAST occurrence, so a stale env value would silently win otherwise
    _flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
              if "force_host_platform_device_count" not in f]
    os.environ["XLA_FLAGS"] = " ".join(
        ["--xla_force_host_platform_device_count=8"] + _flags)

import argparse  # noqa: E402
import json  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Dict  # noqa: E402

from benchmarks.common import emit  # noqa: E402
from repro.core.fleet import (DEFAULT_PROFILES, FleetSpec, RegionSpec,  # noqa: E402
                              build_fleet, run_fleet)
from repro.data.workload import (DEFAULT_TIERS, FunctionCallWorkload,  # noqa: E402
                                 build_catalog)

QOS_PR4_CARBON_G = 0.00273   # qos_fleet tiered pressure figure (PR 4)
FORCED_DEVICES = 8

# (name, paper week, CI scale, share of fleet capacity): per-region CI
# traces come from the paper weeks scaled clean/dirty, and a real fleet
# sizes capacity toward clean grids — the router then keeps most traffic
# there and spills to dirtier regions only under queue pressure
REGION_BASES = (
    ("clean", "week2", 0.4, 0.40),
    ("mid-a", "week3", 0.5, 0.25),
    ("mid-b", "week4", 0.7, 0.20),
    ("dirty", "week1", 1.2, 0.15),
)


def build_scale_spec(n_pods: int) -> FleetSpec:
    """Spread `n_pods` over the four regions by capacity share with a
    heterogeneous profile mix; the clean region hosts the sharded pod (it
    attracts the batch tier, which is what the extra decode bandwidth is
    for)."""
    per_region = [max(1, round(n_pods * share))
                  for _, _, _, share in REGION_BASES]
    while sum(per_region) > n_pods:
        per_region[per_region.index(max(per_region))] -= 1
    while sum(per_region) < n_pods:
        per_region[0] += 1
    regions = []
    for (name, week, scale, _), count in zip(REGION_BASES, per_region):
        if count == 0:           # tiny fleets: drop the region entirely
            continue
        mix = []
        if name == "clean" and count >= 2:
            mix.append(("pod-dp4", 1))
            count -= 1
        # mostly 4-slot pods: high decode occupancy is where the shared-step
        # energy split (and therefore carbon/query) wins
        big = count - count // 3
        if big:
            mix.append(("pod", big))
        if count - big:
            mix.append(("edge", count - big))
        regions.append(RegionSpec(name, week=week, ci_scale=scale,
                                  pods=tuple(mix)))
    return FleetSpec(regions=tuple(regions), profiles=DEFAULT_PROFILES)


def _decode_tps(engine) -> float:
    """Whole-run decode TPS from the engine's own telemetry."""
    return engine.recent_tps(window=len(engine.step_log))


def run_fleet_at(n_pods: int, *, qph: float, n_steps: int = 2,
                 seed: int = 0) -> Dict:
    fleet = build_fleet(build_scale_spec(n_pods), seed=seed)
    catalog = build_catalog(32, seed=seed)
    wl = FunctionCallWorkload(catalog, seed=5, tiers=DEFAULT_TIERS)
    recs = run_fleet(fleet, wl, n_steps=n_steps, queries_per_hour=qph,
                     seed=1, backend="engine")
    flat = [r for rs in recs.values() for r in rs]
    built = fleet.built_pods()
    # pods decode in parallel on the shared fleet clock: aggregate decode
    # capacity is the sum of each pod's achieved decode rate
    agg_tps = sum(_decode_tps(p.client.engine) for p in built)
    profile_tps: Dict[str, Dict] = {}
    for p in built:
        d = profile_tps.setdefault(
            p.profile, {"pods": 0, "decode_tps_per_pod": 0.0,
                        "data_shards": p.client.engine.data_shards})
        d["pods"] += 1
        d["decode_tps_per_pod"] += _decode_tps(p.client.engine)
    for d in profile_tps.values():
        d["decode_tps_per_pod"] /= max(d["pods"], 1)
    # routing-time counts (include queries that later expire/fail — the
    # completion-side view is PodState.served)
    region_routed = {r.name: r.routed for r in fleet.regions}
    return {
        "n_pods": n_pods,
        "built_pods": len(built),
        "queries": len(flat),
        "agg_decode_tps": agg_tps,
        "carbon_g_per_query": (sum(r.carbon_g for r in flat)
                               / max(len(flat), 1)),
        "region_routed": region_routed,
        "profiles": profile_tps,
    }


def run(quiet: bool = False) -> Dict:
    # saturating tiered traffic: the SAME arrival stream at every pod count,
    # heavy enough that even 16 pods run their decode slots at high
    # occupancy (shared-step energy split) while 4 pods queue deeply
    qph = 1440.0
    by_pods: Dict[str, Dict] = {}
    for n in (4, 16):
        r = run_fleet_at(n, qph=qph)
        by_pods[str(n)] = r
        if not quiet:
            emit(f"fleet_scale/pods/{n}", r["agg_decode_tps"],
                 f"built={r['built_pods']} "
                 f"CF/query={r['carbon_g_per_query'] * 1000:.2f}mg "
                 f"regions={r['region_routed']}")
    scaling = (by_pods["16"]["agg_decode_tps"]
               / max(by_pods["4"]["agg_decode_tps"], 1e-9))
    prof16 = by_pods["16"]["profiles"]
    sharded = {
        "enabled": any(d.get("data_shards", 1) > 1 for d in prof16.values()),
        "profiles": prof16,
    }
    cf16 = by_pods["16"]["carbon_g_per_query"]
    acceptance = {
        "tps_scaling_4_to_16": scaling,
        "tps_scaling_ge_3x": bool(scaling >= 3.0),
        "carbon_g_per_query": cf16,
        "qos_pr4_carbon_g": QOS_PR4_CARBON_G,
        "carbon_le_qos_pr4": bool(cf16 <= QOS_PR4_CARBON_G),
        "pass": bool(scaling >= 3.0 and cf16 <= QOS_PR4_CARBON_G),
    }
    if not quiet:
        emit("fleet_scale/scaling_4_to_16", scaling,
             f"sharded={sharded['enabled']} pass={acceptance['pass']}")
    return {"pods": by_pods, "sharded": sharded, "acceptance": acceptance}


def json_summary() -> Dict:
    """CI artifact entrypoint. The sharded profile needs forced host
    devices, which must be set before jax initializes — when this process
    is too late for that (run.py imported other suites first), re-exec in a
    clean subprocess and collect its JSON. Forced devices are CPU devices,
    and a child could not open a chip this process already holds, so off
    the CPU this refuses instead of re-executing."""
    import jax
    if jax.default_backend() != "cpu":
        raise RuntimeError(
            "fleet_scale runs its fleet on forced CPU host devices; this "
            f"process already holds the {jax.default_backend()!r} backend, "
            "so a child cannot take over. Run it under JAX_PLATFORMS=cpu.")
    if jax.device_count() >= FORCED_DEVICES:
        return run(quiet=True)
    with tempfile.NamedTemporaryFile(suffix=".json", delete=False) as f:
        out_path = f.name
    try:
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(
            [os.path.join(repo, "src"), repo]
            + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
        subprocess.run([sys.executable, os.path.abspath(__file__),
                        "--json", out_path, "--quiet"],
                       check=True, env=env, cwd=repo)
        with open(out_path) as f:
            return json.load(f)
    finally:
        os.unlink(out_path)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None,
                    help="write results JSON (CI perf-trajectory artifact)")
    ap.add_argument("--quiet", action="store_true")
    args = ap.parse_args()
    out = run(quiet=args.quiet)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=2, sort_keys=True)


if __name__ == "__main__":
    main()
