"""Policy atlas: which policy wins where, across the scenario space.

Memos (PAPERS.md) shows hybrid-memory policy rankings INVERT across access
patterns; the paper's Figs. 7-15 compare Rainbow vs the HSCC baselines on the
app table only. This benchmark generalizes that comparison to every
registered workload scenario (repro.workloads.scenarios): a
(scenario x policy-preset x ControlPolicy-knob x seed) grid streamed through
the fleet as FUSED cells (traces synthesized inside the sharded scan), with
journal resume — at full scale (BENCH_QUICK=0) all 19 scenarios x 6 policy
columns x 3 seeds.

The run leans on the whole atlas-scale fast path: every (scenario, preset)
pair is its own compile signature, so the CompileCache + persistent
compilation cache (repro.utils.compile_cache) decide whether a repeat/resumed
atlas recompiles anything; the prefetch pipeline stages ahead; the journal
batches retirement I/O.

Outputs:
  - rendered which-policy-wins-where matrix (mean IPC per cell, winner
    starred) on stdout
  - BENCH_atlas.json: config, per-cell rows, matrix, winners, per-group
    GroupTiming rows (this run + everything the journal accumulated),
    compile-cache stats, cells/sec

CLI (ci.sh runs the 2x2x2 smoke):
  PYTHONPATH=src python -m benchmarks.policy_atlas \\
      --scenarios 2 --policies 2 --seeds 2 --journal /tmp/atlas.jsonl \\
      --out BENCH_atlas.json --resume-check
"""
from __future__ import annotations

import argparse
import json
import os
import sys

if (
    __name__ == "__main__"
    and "jax" not in sys.modules
    and "host_platform_device_count" not in os.environ.get("XLA_FLAGS", "")
):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=4"
    ).strip()

import time

import numpy as np

from benchmarks.common import QUICK, emit

# Quick-mode scenario picks: one skewed, one streaming, one drifting — the
# regimes where rankings are known to diverge. Full mode sweeps the registry.
QUICK_SCENARIOS = ["stress/zipf-hotspot", "stress/seq-scan",
                   "stress/phase-shift"]
INTERVALS = 2 if QUICK else 4
ACCESSES = 1200 if QUICK else 20_000
SEEDS = (0, 1) if QUICK else (0, 1, 2)


def _policy_columns(mc):
    """(column label, engine policy kind, ControlPolicy override | None).

    The first four are the paper's comparison (Rainbow vs HSCC 4KB/2MB vs the
    flat baseline); the knob variants probe the ControlPolicy axis the
    ISSUE's Memos motivation cares about (does doubling the hot-set monitor
    or retaining counter history change who wins?).
    """
    from repro.engine.policy import get_policy

    rb = get_policy("sim-rainbow", mc=mc)
    return [
        ("rainbow", "rainbow", None),
        ("hscc-4kb", "hscc-4kb-mig", None),
        ("hscc-2mb", "hscc-2mb-mig", None),
        ("flat-static", "flat-static", None),
        ("rainbow/top_n-x2", "rainbow", rb.replace(top_n=2 * mc.top_n)),
        ("rainbow/decay-0.5", "rainbow", rb.replace(counter_decay=0.5)),
    ]


def build_plan(scenarios, columns, seeds, intervals, accesses):
    """One SweepPlan for the whole atlas: one grid per policy column, added.

    Per-kind grids are REQUIRED by SweepPlan.grid (a single ControlPolicy
    override cannot span policy kinds whose knobs use different units);
    the column label rides on each cell as a ("variant", ...) tag.
    """
    from repro.engine import fleet

    plan = None
    for label, kind, control in columns:
        grid = fleet.SweepPlan.grid(
            policies=[kind], seeds=tuple(seeds), scenario=tuple(scenarios),
            intervals=intervals, accesses=accesses, policy=control,
            tags=(("variant", label),),
        )
        plan = grid if plan is None else plan + grid
    return plan


def _rows(cells_metrics):
    return [
        {
            "scenario": c.app,
            "variant": c.tag["variant"],
            "seed": c.seed,
            "ipc": m.ipc,
            "mpki": m.mpki,
            "total_cycles": m.total_cycles,
            "migrations": m.migrations,
            "mig_bytes": m.mig_bytes,
            "tlb_service_frac": m.tlb_service_frac,
        }
        for c, m in cells_metrics
    ]


def _matrix(rows, scenarios, columns):
    """{scenario: {column: mean IPC across seeds}} + per-scenario winner."""
    mat: dict[str, dict[str, float]] = {}
    for scen in scenarios:
        mat[scen] = {}
        for label, _, _ in columns:
            vals = [r["ipc"] for r in rows
                    if r["scenario"] == scen and r["variant"] == label]
            mat[scen][label] = float(np.mean(vals)) if vals else float("nan")
    winners = {scen: max(cols, key=cols.get) for scen, cols in mat.items()}
    return mat, winners


def render_matrix(mat, winners) -> str:
    """The which-policy-wins-where table (winner starred per scenario row)."""
    cols = list(next(iter(mat.values())))
    w0 = max(len("scenario"), *(len(s) for s in mat))
    widths = [max(len(c), 10) for c in cols]
    lines = [
        " | ".join(["scenario".ljust(w0)]
                   + [c.rjust(w) for c, w in zip(cols, widths)]),
        "-+-".join(["-" * w0] + ["-" * w for w in widths]),
    ]
    for scen, by_col in mat.items():
        cells = []
        for c, w in zip(cols, widths):
            star = "*" if winners[scen] == c else " "
            cells.append(f"{star}{by_col[c]:.4f}".rjust(w))
        lines.append(" | ".join([scen.ljust(w0)] + cells))
    return "\n".join(lines)


def run_atlas(scenarios=None, n_policies=None, seeds=None, intervals=None,
              accesses=None, journal=None, out_path="BENCH_atlas.json",
              resume_check=False, quiet=False) -> dict:
    import jax

    from repro.engine import fleet
    from repro.sim.config import MachineConfig
    from repro.workloads.scenarios import available_scenarios

    mc = MachineConfig()
    if scenarios is None:
        scenarios = QUICK_SCENARIOS if QUICK else list(available_scenarios())
    columns = _policy_columns(mc)
    if n_policies is not None:
        columns = columns[:n_policies]
    seeds = tuple(seeds if seeds is not None else SEEDS)
    intervals = intervals or INTERVALS
    accesses = accesses or ACCESSES

    plan = build_plan(scenarios, columns, seeds, intervals, accesses)
    runner = fleet.FleetRunner()
    t0 = time.perf_counter()
    pairs = list(runner.run_iter(plan, journal=journal))
    elapsed = time.perf_counter() - t0

    rows = _rows(pairs)
    mat, winners = _matrix(rows, scenarios, columns)
    executed = sum(t.cells for t in runner.timings)
    timings = [t.row() for t in runner.timings]
    journal_timings = (
        fleet.FleetJournal(journal).load_timings() if journal else []
    )

    if resume_check:
        # A fresh runner over the same plan+journal must replay EVERY cell
        # from disk (zero groups staged/executed) and reproduce the metrics.
        runner2 = fleet.FleetRunner()
        pairs2 = list(runner2.run_iter(plan, journal=journal))
        assert dict(pairs2) == dict(pairs), "resumed atlas diverged"
        assert not runner2.timings, (
            f"resume re-executed {len(runner2.timings)} groups instead of "
            "replaying the journal"
        )
        if not quiet:
            print(f"resume check OK: {len(pairs2)} cells replayed, "
                  "0 groups re-executed")

    result = {
        "config": {
            "scenarios": list(scenarios),
            "policies": [label for label, _, _ in columns],
            "seeds": list(seeds),
            "intervals": intervals,
            "accesses": accesses,
            "devices": len(jax.devices()),
            "journal": str(journal) if journal else None,
        },
        "rows": rows,
        "matrix": mat,
        "winners": winners,
        "timings": timings,
        "journal_timings": journal_timings,
        "compile_cache": runner.compile_cache.stats(),
        "elapsed_s": round(elapsed, 3),
        "cells": len(rows),
        "cells_executed": executed,
        "cells_per_sec": round(len(rows) / elapsed, 3),
    }
    if out_path:
        with open(out_path, "w") as f:
            json.dump(result, f, indent=1)
    if not quiet:
        print(render_matrix(mat, winners))
        print(f"winners: { {s: w for s, w in winners.items()} }")
    return result


def run() -> None:
    t0 = time.time()
    out = run_atlas(out_path="BENCH_atlas.json")
    flat = [
        {"scenario": s, **{c: round(v, 4) for c, v in cols.items()},
         "winner": out["winners"][s]}
        for s, cols in out["matrix"].items()
    ]
    inversions = len(set(out["winners"].values()))
    emit(
        "policy_atlas", flat, t0,
        derived=(
            f"cells={out['cells']};cells_per_sec={out['cells_per_sec']};"
            f"distinct_winners={inversions};"
            f"compile_hits={out['compile_cache']['hits']};"
            f"compile_misses={out['compile_cache']['misses']}"
        ),
    )


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--scenarios", default=None,
                   help="comma-separated scenario names, or a count to take "
                        "the first N registered")
    p.add_argument("--policies", type=int, default=None,
                   help="use the first N policy columns")
    p.add_argument("--seeds", type=int, default=None,
                   help="seeds 0..N-1")
    p.add_argument("--intervals", type=int, default=None)
    p.add_argument("--accesses", type=int, default=None)
    p.add_argument("--journal", default=None,
                   help="journal path: stream + checkpoint; resumable")
    p.add_argument("--out", default="BENCH_atlas.json")
    p.add_argument("--resume-check", action="store_true",
                   help="after the run, replay the journal with a fresh "
                        "runner and assert bit-identical, zero re-execution")
    args = p.parse_args(argv)

    scenarios = None
    if args.scenarios:
        if args.scenarios.isdigit():
            from repro.workloads.scenarios import available_scenarios

            scenarios = list(available_scenarios())[: int(args.scenarios)]
        else:
            scenarios = args.scenarios.split(",")
    seeds = tuple(range(args.seeds)) if args.seeds else None
    run_atlas(scenarios=scenarios, n_policies=args.policies, seeds=seeds,
              intervals=args.intervals, accesses=args.accesses,
              journal=args.journal, out_path=args.out,
              resume_check=args.resume_check)


if __name__ == "__main__":
    main()
