"""Microbenchmark: interval-control-loop throughput (accesses/sec).

Compares three ways of driving the same Rainbow simulation:

  looped-host     — the pre-refactor path: per-interval host trace generation +
                    one device dispatch per interval + eager (unjitted) Python
                    controller round-trips (sim.runner.simulate_eager).
  scanned-device  — the MemoryEngine: traces pre-generated and staged once,
                    the full simulation runs as a single lax.scan jit
                    (engine.simloop.engine_run); steady-state scan time.
  scanned+fused   — same scan with the fused one-pass counting kernel path
                    ("ref" oracle off-TPU, the Pallas kernel on TPU).

Then two PR 7 hot-path artifacts:

  per-phase profile — `engine_run(..., profile=True)`: where each interval's
      wall time goes (tlb walk / observe / plan / apply), with XLA
      compiled-cost analysis per phase (engine.profile; docs/engine.md).
  HOT-PATH GATE — warm `engine_run` with the vectorized fast path
      (EngineSpec.fastpath=True, the default) vs the pre-overhaul reference
      ops (fastpath=False: per-access serial lookups, full argsort selection,
      per-vpn shootdown scan, f32 histogram adds).  Both legs run in this
      process, one after the other (a chip belongs to one process at a
      time); each keeps its per-interval stats + final counters, and the
      gate ASSERTS the legs are bit-identical and that the rainbow fast path
      clears GATE_FLOOR x the reference.

Results land in BENCH_engine.json at the repo root (aggregated by
benchmarks.run, schema-checked by scripts/ci.sh).

Run: PYTHONPATH=src python -m benchmarks.engine_throughput
"""
from __future__ import annotations

import time

import jax
import numpy as np

from benchmarks.common import QUICK, emit, write_bench_json
from repro.sim.config import MachineConfig
from repro.sim.runner import simulate_eager

APP = "streamcluster"
POLICY = "rainbow"
INTERVALS = 6 if QUICK else 10
ACCESSES = 20_000 if QUICK else 120_000
SEED = 7

# Hot-path gate: the floor applies to the headline rainbow leg (the paper's
# system — TLB walk + bitmap cache + monitor/plan/apply all active); the
# other policies ride along for bit-identity and informational speedups.
GATE_FLOOR = 1.4
GATE_POLICIES = ("rainbow", "flat-static", "hscc-4kb-mig")


def _bench(fn, reps: int = 3) -> float:
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _measure() -> dict:
    from repro.engine import simloop

    mc = MachineConfig()
    total_accesses = INTERVALS * ACCESSES

    # --- looped host (one interval per dispatch; includes per-interval
    # trace generation, exactly as the pre-refactor runner executed) ---
    simulate_eager(APP, POLICY, mc, intervals=1, accesses=ACCESSES, seed=SEED)  # warm caches
    t_host = _bench(
        lambda: simulate_eager(
            APP, POLICY, mc, intervals=INTERVALS, accesses=ACCESSES, seed=SEED
        ),
        reps=1 if QUICK else 2,
    )

    rows = [{
        "mode": "looped-host",
        "intervals": INTERVALS,
        "accesses_per_interval": ACCESSES,
        "seconds": round(t_host, 4),
        "accesses_per_sec": round(total_accesses / t_host, 1),
    }]

    # --- scanned device engine (counting backends) ---
    backends = ["jax", "ref"] + (["pallas"] if jax.default_backend() == "tpu" else [])
    results = {"looped-host": total_accesses / t_host}
    chunks, meta = simloop.make_chunks(APP, POLICY, mc, SEED, INTERVALS, ACCESSES)
    for backend in backends:
        spec = simloop.EngineSpec(
            policy=POLICY, mc=mc,
            num_superpages=meta["num_superpages"],
            footprint_pages=meta["footprint_pages"],
            counter_backend=backend,
        )
        state0 = simloop.engine_init(spec)
        out = simloop.engine_run(spec, state0, chunks)  # compile + warm
        jax.block_until_ready(out)

        def scan_once():
            jax.block_until_ready(simloop.engine_run(spec, state0, chunks))

        t_scan = _bench(scan_once)
        mode = "scanned-device" if backend == "jax" else f"scanned+fused({backend})"
        rows.append({
            "mode": mode,
            "intervals": INTERVALS,
            "accesses_per_interval": ACCESSES,
            "seconds": round(t_scan, 4),
            "accesses_per_sec": round(total_accesses / t_scan, 1),
        })
        results[mode] = total_accesses / t_scan

    speedup = results["scanned-device"] / results["looped-host"]
    return {"rows": rows, "speedup": speedup}


# ---------------------------------------------------------------------------
# Per-phase profile (engine.profile via engine_run(..., profile=True))
# ---------------------------------------------------------------------------


def _profile() -> dict:
    """Phase-attributed interval costs for the headline rainbow workload."""
    from repro.engine import simloop

    mc = MachineConfig()
    chunks, meta = simloop.make_chunks(APP, POLICY, mc, SEED, INTERVALS, ACCESSES)
    spec = simloop.EngineSpec(
        policy=POLICY, mc=mc,
        num_superpages=meta["num_superpages"],
        footprint_pages=meta["footprint_pages"],
    )
    _, _, prof = simloop.engine_run(
        spec, simloop.engine_init(spec), chunks, profile=True
    )
    d = prof.as_dict()
    total_wall = sum(p["wall_s"] for p in d["phases"].values()) or 1.0
    rows = [
        {
            "phase": name,
            "wall_s": round(p["wall_s"], 4),
            "wall_frac": round(p["wall_s"] / total_wall, 3),
            "compile_s": round(p["compile_s"], 4),
            "calls": p["calls"],
            "gflops_per_call": round(p["flops"] / 1e9, 4),
            "mbytes_per_call": round(p["bytes_accessed"] / 1e6, 3),
        }
        for name, p in d["phases"].items()
    ]
    return {"rows": rows, "profile": d}


# ---------------------------------------------------------------------------
# Hot-path gate (fastpath=True vs fastpath=False, in this process)
# ---------------------------------------------------------------------------


def _gate_leg(mode: str) -> dict:
    """One gate leg: warm engine_run per policy + digest.

    `mode` selects the compiled program: "fast" = the PR 7 vectorized hot
    path (EngineSpec default), "reference" = the pre-overhaul ops kept under
    fastpath=False.  The digest (per-interval stats + final counters, exact
    float64 of the f32 values) lets the parent assert bit-identity.
    """
    from repro.engine import simloop

    fastpath = mode == "fast"
    mc = MachineConfig()
    legs = {}
    for policy in GATE_POLICIES:
        chunks, meta = simloop.make_chunks(
            APP, policy, mc, SEED, INTERVALS, ACCESSES
        )
        spec = simloop.EngineSpec(
            policy=policy, mc=mc,
            num_superpages=meta["num_superpages"],
            footprint_pages=meta["footprint_pages"],
            fastpath=fastpath,
        )
        state0 = simloop.engine_init(spec)
        state, stats = simloop.engine_run(spec, state0, chunks)  # compile + warm
        jax.block_until_ready((state, stats))
        t = _bench(
            lambda: jax.block_until_ready(
                simloop.engine_run(spec, state0, chunks)
            ),
            reps=3 if QUICK else 2,
        )
        digest = [
            np.asarray(x, np.float64).reshape(-1).tolist() for x in stats
        ] + [float(np.asarray(c)) for c in state.sim.counters]
        legs[policy] = {"seconds": t, "digest": digest}
    return legs


def _gate() -> dict:
    """Run both legs; assert bit-identity + the rainbow floor."""
    ref = _gate_leg("reference")
    fast = _gate_leg("fast")
    total_accesses = INTERVALS * ACCESSES
    rows, per_policy = [], {}
    for policy in GATE_POLICIES:
        a, b = ref[policy], fast[policy]
        assert a["digest"] == b["digest"], (
            f"hot-path gate FAILED: fastpath SimMetrics inputs diverge "
            f"from the reference ops on {policy}"
        )
        sp = a["seconds"] / b["seconds"]
        per_policy[policy] = {
            "reference_s": round(a["seconds"], 4),
            "fast_s": round(b["seconds"], 4),
            "speedup": round(sp, 3),
            "accesses_per_sec": round(total_accesses / b["seconds"], 1),
        }
        rows.append({
            "policy": policy,
            "intervals": INTERVALS,
            "accesses_per_interval": ACCESSES,
            "reference_s": round(a["seconds"], 4),
            "fast_s": round(b["seconds"], 4),
            "speedup": round(sp, 3),
            "bit_identical": True,
        })
    speedup = per_policy[POLICY]["speedup"]
    if speedup < GATE_FLOOR:
        raise RuntimeError(
            f"engine hot-path gate FAILED: fastpath warm engine_run is "
            f"only {speedup:.2f}x the pre-overhaul reference on {POLICY} "
            f"(floor: {GATE_FLOOR}x)"
        )
    return {
        "rows": rows,
        "speedup": speedup,
        "per_policy": per_policy,
        "floor": GATE_FLOOR,
    }


def run() -> None:
    t0 = time.time()
    out = _measure()
    emit(
        "engine_throughput", out["rows"], t0,
        derived=f"scanned_vs_host_speedup={out['speedup']:.1f}x",
    )
    t1 = time.time()
    prof = _profile()
    emit("engine_profile", prof["rows"], t1,
         derived=f"intervals={INTERVALS};policy={POLICY}")
    t2 = time.time()
    gate = _gate()
    emit(
        "engine_hotpath_gate", gate["rows"], t2,
        derived=(
            f"fastpath_vs_reference={gate['speedup']:.2f}x"
            f"(floor {GATE_FLOOR}x);policies={len(GATE_POLICIES)}"
        ),
    )
    write_bench_json("engine", {
        "unit": "accesses_per_sec",
        "app": APP,
        "policy": POLICY,
        "intervals": INTERVALS,
        "accesses_per_interval": ACCESSES,
        "rows": out["rows"],
        "scanned_vs_host_speedup": round(out["speedup"], 3),
        "profile": prof["profile"],
        "gate": {
            "floor": GATE_FLOOR,
            "speedup": gate["speedup"],
            "per_policy": gate["per_policy"],
            "bit_identical": True,
        },
        "headline": (
            f"fastpath {gate['speedup']:.2f}x reference warm engine_run "
            f"(floor {GATE_FLOOR}x), bit-identical on "
            f"{len(GATE_POLICIES)} policies"
        ),
    })


if __name__ == "__main__":
    run()
