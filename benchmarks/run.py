"""Run the full benchmark suite:  python -m benchmarks.run [--full]

One benchmark per paper figure (Fig 2, Fig 3a/3b/3c) plus the
trajectory benches (fused / timegates / sources / replay / resilience /
scenarios).
"""

from __future__ import annotations

import argparse
import json
import time

from repro.launch.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="larger photon counts (slower)")
    args = ap.parse_args()
    quick = not args.full
    enable_compile_cache()

    from benchmarks import (fig2_optimizations, fig3a_workgroup,
                            fig3b_devicelb, fig3c_scaling, fused, replay,
                            resilience, scenarios, sources, timegates)

    t0 = time.time()
    results = {}
    print("=" * 70)
    print("Fig 2 — optimization ladder (B1/B2/B2a x Baseline/Opt1/+2/+3)")
    print("=" * 70, flush=True)
    results["fig2"] = fig2_optimizations.run(quick=quick)

    print("=" * 70)
    print("Fig 3a — thread-level vs workgroup-level load balancing")
    print("=" * 70, flush=True)
    results["fig3a"] = fig3a_workgroup.run(quick=quick)

    print("=" * 70)
    print("Fig 3b — device-level partitioning S1/S2/S3")
    print("=" * 70, flush=True)
    results["fig3b"] = fig3b_devicelb.run(quick=quick)

    print("=" * 70)
    print("Fig 3c — multi-device scaling 1x..8x")
    print("=" * 70, flush=True)
    results["fig3c"] = fig3c_scaling.run(quick=quick)

    print("=" * 70)
    print("Fused rounds — photons/s vs K = steps_per_round, per engine")
    print("=" * 70, flush=True)
    results["fused"] = fused.run(quick=quick)

    print("=" * 70)
    print("Time gates — photons/s vs n_time_gates, per engine")
    print("=" * 70, flush=True)
    results["timegates"] = timegates.run(quick=quick)

    print("=" * 70)
    print("Sources — per-source-type launch/regeneration cost")
    print("=" * 70, flush=True)
    results["sources"] = sources.run(quick=quick)

    print("=" * 70)
    print("Replay — detected-photon recording overhead + Jacobian replay")
    print("=" * 70, flush=True)
    results["replay"] = replay.run(quick=quick)

    print("=" * 70)
    print("Resilience — fault-free DevicePool overhead vs pre-PR scheduler")
    print("=" * 70, flush=True)
    results["resilience"] = resilience.run(quick=quick)

    print("=" * 70)
    print("Scenarios — batched multi-scenario scenarios/s + cache hit rate")
    print("=" * 70, flush=True)
    results["scenarios"] = scenarios.run(quick=quick)

    print(f"\nbenchmark suite done in {time.time()-t0:.1f}s")
    with open("bench_results.json", "w") as f:
        json.dump(results, f, indent=2, default=str)
    print("wrote bench_results.json")


if __name__ == "__main__":
    main()
