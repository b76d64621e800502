"""Benchmark driver: one module per paper table/figure + the roofline table.

``PYTHONPATH=src python -m benchmarks.run``                 (quick mode)
``BENCH_QUICK=0 PYTHONPATH=src python -m benchmarks.run``   (full workload table)

Each module prints its rows as CSV plus a ``name,us_per_call,derived`` line,
where `derived` carries the paper-claim comparison for EXPERIMENTS.md.
"""
from __future__ import annotations

import glob
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODULES = (
    "paper_table6_storage",  # cheap first
    "paper_fig1_table12",
    "paper_fig7_mpki",
    "paper_fig8_tlb_cycles",
    "paper_fig9_breakdown",
    "paper_fig10_ipc",
    "paper_fig11_traffic",
    "paper_fig12_energy",
    "paper_fig15_runtime",
    "paper_fig13_14_sensitivity",
    "engine_throughput",
    "fleet_throughput",
    "timing_contention",
    "nomad_async",
    "policy_atlas",
    "serving_rainbow",
    "autotune_serving",
    "roofline",
)


def aggregate() -> list[str]:
    """Summarize every BENCH_*.json the modules wrote at the repo root.

    Each file carries a `headline` string and (when the module has a floor)
    a `gate` object with `floor` + `speedup`; this prints the one-screen
    roll-up the CI log and EXPERIMENTS.md link to.

    Returns the list of failures (an unreadable BENCH file or a gate whose
    `speedup` fell below its `floor`) — callers MUST treat a non-empty list
    as a hard failure. Before this returned anything, a regressed gate
    printed "[gate FAIL]" into a green CI log and nobody looked; now
    `main()` and `--aggregate-only` both exit non-zero on it.
    """
    failures: list[str] = []
    paths = sorted(glob.glob(os.path.join(ROOT, "BENCH_*.json")))
    if not paths:
        return failures
    print("\n===== BENCH_*.json aggregate =====")
    for p in paths:
        name = os.path.basename(p)
        try:
            with open(p) as f:
                d = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            print(f"{name}: unreadable ({e})")
            failures.append(f"{name}: unreadable ({e})")
            continue
        gate = d.get("gate") or {}
        status = ""
        if "floor" in gate and "speedup" in gate:
            ok = gate["speedup"] >= gate["floor"]
            status = f" [gate {'PASS' if ok else 'FAIL'}]"
            if not ok:
                failures.append(
                    f"{name}: gate speedup {gate['speedup']} < floor "
                    f"{gate['floor']}"
                )
        print(f"{name}: {d.get('headline', '(no headline)')}{status}")
    return failures


def main() -> None:
    if "--aggregate-only" in sys.argv[1:]:
        # gate check over already-written BENCH files (scripts/ci.sh runs
        # this after the benchmark legs; no benchmarks are re-run)
        gate_failures = aggregate()
        if gate_failures:
            print(f"\nFAILED gates: {gate_failures}")
            sys.exit(1)
        print("\nall BENCH gates pass")
        return
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [os.path.join(ROOT, "src"), ROOT, os.environ.get("PYTHONPATH", "")]))
    failed = []
    for name in MODULES:
        print(f"\n===== {name} =====", flush=True)
        # one process per module, and none of JAX here: a chip belongs to
        # one process at a time, and fleet_throughput starts its own
        if subprocess.run([sys.executable, "-m", f"benchmarks.{name}"],
                          cwd=ROOT, env=env).returncode:
            failed.append(name)
    failed += aggregate()
    if failed:
        print(f"\nFAILED benchmarks: {failed}")
        sys.exit(1)
    print("\nall benchmarks completed")


if __name__ == "__main__":
    main()
