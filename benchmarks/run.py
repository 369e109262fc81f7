"""Benchmark harness: one module per paper table/figure (+ fabric sweeps).

    PYTHONPATH=src python -m benchmarks.run [--only table2] [--smoke]

``--smoke`` runs every module for one tiny iteration (CI-friendly).
Prints ``name,value,derived`` CSV rows, then a per-benchmark PASS/FAIL
summary on stderr; exits non-zero if any benchmark raised.

Benchmarks that emit a ``BENCH_*.json`` artifact have it *deleted before
they run*: a benchmark that dies mid-list must leave no stale artifact
behind for CI to upload as if it were fresh (the upload step then fails on
the missing file instead).
"""
from __future__ import annotations

import argparse
import inspect
import os
import sys
import time
import traceback

MODULES = [
    "table2_state_sizes",         # Table II
    "fig5_fig6_policy_speedups",  # Figs 5-6
    "fig8_fig9_ratio",            # Figs 8-9
    "fig10_migration_counts",     # Fig 10
    "fig11_knowledge_policy",     # Fig 11
    "bench_fabric",               # N-env fabric / pipeline / scheduler
    "bench_state_plane",          # CAS chunk delta vs whole-name baseline
    "bench_context",              # interaction models / prefetch gate
    "bench_fleet",                # event-driven fleet: arrivals/failures/scaling
    "bench_transport",            # wire protocol: loopback vs socket vs shaped
    "bench_digest",               # batched digest/delta + zero-copy wire
    "bench_live",                 # background delta replication / liveness
    "bench_gateway",              # persistent gateway: 10k-session storm
    "bench_replica",              # replica plane: failover promotion / racing
    "bench_cost",                 # cost plane: dollars DP / spot / data gravity
]

# module -> the JSON artifact it (re)writes; used for stale-artifact removal
ARTIFACTS = {
    "bench_state_plane": "BENCH_state_plane.json",
    "bench_context": "BENCH_context.json",
    "bench_fleet": "BENCH_fleet.json",
    "bench_transport": "BENCH_transport.json",
    "bench_digest": "BENCH_digest.json",
    "bench_live": "BENCH_live.json",
    "bench_gateway": "BENCH_gateway.json",
    "bench_replica": "BENCH_replica.json",
    "bench_cost": "BENCH_cost.json",
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="one tiny iteration per benchmark")
    args = ap.parse_args()
    results: list[tuple[str, bool, float]] = []
    print("name,value,derived")
    for modname in MODULES:
        if args.only and args.only not in modname:
            continue
        artifact = ARTIFACTS.get(modname)
        if artifact and os.path.exists(artifact):
            os.remove(artifact)          # never upload a stale report
        t0 = time.perf_counter()
        ok = True
        try:
            mod = __import__(f"benchmarks.{modname}", fromlist=["run"])
            kw = {}
            if args.smoke and "smoke" in inspect.signature(mod.run).parameters:
                kw["smoke"] = True
            for name, val, note in mod.run(**kw):
                print(f"{name},{val},{note}")
        except Exception:  # noqa: BLE001
            ok = False
            traceback.print_exc()
        results.append((modname, ok, time.perf_counter() - t0))
    print("#\n# summary:", file=sys.stderr)
    for modname, ok, secs in results:
        print(f"#   {modname:<28} {'PASS' if ok else 'FAIL':<4} {secs:6.1f}s",
              file=sys.stderr)
    failures = sum(1 for _, ok, _ in results if not ok)
    if failures:
        print(f"# {failures} benchmark(s) failed", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
