"""Layer-A experiment runner: a thin host shell over the device-resident
MemoryEngine (engine.simloop), aggregating the paper's metrics (MPKI,
TLB-service cycles, IPC, migration traffic, energy, translation breakdown).

Two execution paths produce SimMetrics:

  simulate(...)              — default: pre-generate all interval traces, run
                               the whole simulation as one lax.scan on device
                               (engine.simloop.engine_run), finalize on host.
  simulate(..., engine=False)— the pre-refactor eager reference: one host
                               round-trip per interval through sim.policies.
                               Kept as the equivalence oracle (tests/test_engine
                               asserts bit-identical metrics) and as the
                               baseline of benchmarks/engine_throughput.py.

`sweep` declares the (app x policy x seed) grid as an engine.fleet.SweepPlan
and runs it through the mesh-sharded FleetRunner — same-shape cells fuse into
one sharded fleet axis, trace staging double-buffers against the device scan.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import numpy as np

from repro.sim import trace as trace_mod
from repro.sim.config import APPS, MIXES, MachineConfig
from repro.sim.energy import energy_joules
from repro.sim.policies import POLICY_CLASSES, interval_costs

BASE_CPI = 0.6  # out-of-order core CPI on non-memory work

_ZERO_TOTALS = {
    "migrations": 0, "evictions": 0, "dirty": 0, "shootdowns": 0,
    "mig_bytes": 0.0, "mig_cycles": 0.0, "shootdown_cycles": 0.0,
    "clflush_cycles": 0.0, "accesses": 0,
    # queueing timing model (repro.timing); exact 0.0 under timing_model="flat"
    "stall_dram": 0.0, "stall_nvm": 0.0, "mig_stall": 0.0,
    "backlog_dram": 0.0, "backlog_nvm": 0.0, "intervals": 0,
    # transactional async migration (engine.nomad); 0 for synchronous policies
    "aborts": 0,
}


@dataclasses.dataclass
class SimMetrics:
    app: str
    policy: str
    instructions: float
    total_cycles: float
    ipc: float
    mpki: float
    tlb_service_cycles: float
    tlb_service_frac: float
    breakdown: dict[str, float]
    migrations: int
    evictions: int
    shootdowns: int
    mig_bytes: float
    footprint_bytes: float
    traffic_ratio: float
    energy: dict[str, float]
    # queueing timing model (EngineSpec.timing_model="queueing"); trailing
    # with defaults so journaled SimMetrics(**fields) round-trips from before
    # the timing subsystem existed. All exact 0.0 under "flat".
    bank_stall_cycles: float = 0.0
    mig_stall_cycles: float = 0.0
    queue_occupancy_dram: float = 0.0
    queue_occupancy_nvm: float = 0.0
    # transactional async migration (engine.nomad): writes to in-flight pages
    # that aborted the copy; exactly 0 for every synchronous policy
    mig_aborts: int = 0

    def row(self) -> dict[str, Any]:
        d = dataclasses.asdict(self)
        d.update(d.pop("breakdown"))
        d.update({f"energy_{k}": v for k, v in d.pop("energy").items()})
        return d


def finalize_metrics(
    app: str,
    policy: str,
    mc: MachineConfig,
    totals: dict,
    counters,
    inst_per_access: float,
    footprint_pages: int,
) -> SimMetrics:
    """Metrics from accumulated per-interval totals + final scan counters."""
    c = counters
    f = lambda x: float(np.asarray(x))
    cycles_trans = (
        f(c.cycles_tlb) + f(c.cycles_walk) + f(c.cycles_bitmap) + f(c.cycles_remap)
    )
    instructions = totals["accesses"] * inst_per_access
    bank_stall = totals["stall_dram"] + totals["stall_nvm"]
    total_cycles = (
        instructions * BASE_CPI
        + cycles_trans
        + f(c.cycles_mem)
        + totals["mig_cycles"]
        + totals["shootdown_cycles"]
        + totals["clflush_cycles"]
        + bank_stall  # exact 0.0 under "flat": total_cycles bitwise unchanged
    )
    # the TLB miss count that matters for MPKI: walks actually taken
    if policy in ("flat-static", "hscc-4kb-mig"):
        tlb_misses = f(c.miss4_l2)
    elif policy in ("hscc-2mb-mig", "dram-only"):
        tlb_misses = f(c.miss2m_l2)
    else:  # rainbow: walks happen only when the superpage TLB misses
        tlb_misses = f(c.miss2m_l2)

    dram_cap = 8.0 if policy == "dram-only" else 1.0
    energy = energy_joules(
        mc,
        f(c.dram_reads), f(c.dram_writes), f(c.nvm_reads), f(c.nvm_writes),
        totals["mig_bytes"], total_cycles, dram_capacity_factor=dram_cap,
    )

    fp_bytes = footprint_pages * 4096.0
    return SimMetrics(
        app=app,
        policy=policy,
        instructions=instructions,
        total_cycles=total_cycles,
        ipc=instructions / total_cycles,
        mpki=tlb_misses / (instructions / 1000.0),
        tlb_service_cycles=cycles_trans,
        tlb_service_frac=cycles_trans / total_cycles,
        breakdown={
            "cycles_tlb": f(c.cycles_tlb),
            "cycles_walk": f(c.cycles_walk),
            "cycles_bitmap": f(c.cycles_bitmap),
            "cycles_remap": f(c.cycles_remap),
            "cycles_mem": f(c.cycles_mem),
            "cycles_mig": totals["mig_cycles"],
            "cycles_shootdown": totals["shootdown_cycles"],
            "cycles_clflush": totals["clflush_cycles"],
            "cycles_bank_stall": bank_stall,
            "bmc_misses": f(c.bmc_miss),
        },
        migrations=totals["migrations"],
        evictions=totals["evictions"],
        shootdowns=totals["shootdowns"],
        mig_bytes=totals["mig_bytes"],
        footprint_bytes=fp_bytes,
        traffic_ratio=totals["mig_bytes"] / fp_bytes,
        energy=energy,
        bank_stall_cycles=bank_stall,
        mig_stall_cycles=totals["mig_stall"],
        queue_occupancy_dram=totals["backlog_dram"] / max(totals["intervals"], 1),
        queue_occupancy_nvm=totals["backlog_nvm"] / max(totals["intervals"], 1),
        mig_aborts=totals["aborts"],
    )


def totals_from_stats(
    policy: str, mc: MachineConfig, stats, accesses_per_interval: int
) -> dict:
    """Accumulate engine per-interval stats in the eager path's order/dtypes."""
    totals = dict(_ZERO_TOTALS)
    m_i = np.asarray(stats.migrations)
    e_i = np.asarray(stats.evictions)
    d_i = np.asarray(stats.dirty_evictions)
    s_i = np.asarray(stats.shootdowns)
    a_i = (
        np.asarray(stats.aborts)
        if stats.aborts is not None
        else np.zeros_like(m_i)
    )
    cols = zip(
        m_i.tolist(), e_i.tolist(), d_i.tolist(), s_i.tolist(),
        np.asarray(stats.stall_dram).tolist(),
        np.asarray(stats.stall_nvm).tolist(),
        np.asarray(stats.mig_stall).tolist(),
        np.asarray(stats.backlog_dram).tolist(),
        np.asarray(stats.backlog_nvm).tolist(),
        a_i.tolist(),
    )
    for m, e, d, s, sd, sn, ms, bd, bn, ab in cols:
        costs = interval_costs(policy, mc, m, e, d, s)
        totals["migrations"] += m
        totals["evictions"] += e
        totals["dirty"] += d
        totals["shootdowns"] += s
        totals["mig_bytes"] += costs["mig_bytes"]
        totals["mig_cycles"] += costs["mig_cycles"]
        totals["shootdown_cycles"] += costs["shootdown_cycles"]
        totals["clflush_cycles"] += costs["clflush_cycles"]
        totals["accesses"] += accesses_per_interval
        totals["stall_dram"] += sd
        totals["stall_nvm"] += sn
        totals["mig_stall"] += ms
        totals["backlog_dram"] += bd
        totals["backlog_nvm"] += bn
        totals["aborts"] += ab
        totals["intervals"] += 1
    return totals


def simulate(
    app: str,
    policy: str,
    mc: MachineConfig | None = None,
    intervals: int = 5,
    accesses: int | None = None,
    seed: int = 7,
    engine: bool = True,
    counter_backend: str = "jax",
    fused: bool = False,
    fastpath: bool = True,
    timing_model: str = "flat",
    queue_geometry=None,
) -> SimMetrics:
    """Simulate (app x policy) over N intervals and aggregate SimMetrics.

    `app` may be a numpy app profile, a mix, or a registered scenario
    (repro.workloads). `fused=True` (scenarios only) synthesizes each
    interval's chunk INSIDE the engine scan instead of staging host-generated
    arrays — bit-identical to the staged path by the workloads differential
    gate (tests/test_workloads.py). `fastpath=False` compiles the engine
    against the pre-overhaul reference ops (EngineSpec.fastpath) — the
    differential anchor for the vectorized hot path.

    `timing_model="queueing"` (+ an optional repro.timing.QueueGeometry)
    charges every interval through the per-channel/bank contention model
    (docs/timing.md); "flat" keeps the event-count cost model bit-identical
    to queueing-with-infinite-banks.
    """
    if not engine:
        if fused:
            raise ValueError("fused generation requires the engine path")
        return simulate_eager(
            app, policy, mc, intervals, accesses, seed,
            timing_model=timing_model, queue_geometry=queue_geometry,
        )
    from repro.engine import simloop  # lazy: sim.__init__ imports this module
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    mc = mc or MachineConfig()
    # Host spans on the profiler's clock: set-up, the device run, host readback.
    with jax.profiler.TraceAnnotation("sim.prepare"):
        if fused:
            from repro.workloads import scenarios as scen

            if not scen.is_scenario(app):
                raise ValueError(
                    f"fused generation needs a registered scenario, got {app!r} "
                    f"(registered: {scen.available_scenarios()}); numpy app "
                    "profiles/mixes run staged"
                )
            meta = trace_mod.probe_meta(app, accesses)
            source = simloop.TraceSource(scenario=app, accesses=accesses)
            chunks = None
        else:
            chunks, meta = simloop.make_chunks(
                app, policy, mc, seed, intervals, accesses
            )
            source = None
        spec = simloop.EngineSpec(
            policy=policy,
            mc=mc,
            num_superpages=meta["num_superpages"],
            footprint_pages=meta["footprint_pages"],
            counter_backend=counter_backend,
            source=source,
            fastpath=fastpath,
            timing_model=timing_model,
            queue_geometry=queue_geometry,
        )
        state0 = simloop.engine_init(spec)
    # The freshly built engine_init state is never reused, so its buffers are
    # donated to the scan — the carry updates in place instead of copying.
    with jax.profiler.TraceAnnotation("sim.run"):
        if fused:
            state, stats = simloop.engine_run_fused(
                spec, state0, seed, intervals, donate=True
            )
        else:
            state, stats = simloop.engine_run(spec, state0, chunks, donate=True)
        # the span ends with the device program, not with its dispatch
        state, stats = jax.block_until_ready((state, stats))
    with jax.profiler.TraceAnnotation("sim.finalize"):
        totals = totals_from_stats(
            policy, mc, stats, meta["accesses_per_interval"]
        )
        return finalize_metrics(
            app, policy, mc, totals, state.sim.counters,
            meta["inst_per_access"], meta["footprint_pages"],
        )


def simulate_eager(
    app: str,
    policy: str,
    mc: MachineConfig | None = None,
    intervals: int = 5,
    accesses: int | None = None,
    seed: int = 7,
    timing_model: str = "flat",
    queue_geometry=None,
) -> SimMetrics:
    """Pre-refactor host-looped reference path (one round-trip per interval)."""
    if policy not in POLICY_CLASSES:
        raise KeyError(
            f"no eager reference for {policy!r}: the numpy HSCC host loops "
            "were deleted after the engine ports passed exact full-table "
            "parity (scripts/validate_hscc_parity.py); use the engine path"
        )
    mc = mc or MachineConfig()
    trace0 = trace_mod.generate(app, seed, 0, accesses)
    pol = POLICY_CLASSES[policy](
        mc, trace0, seed,
        timing_model=timing_model, queue_geometry=queue_geometry,
    )

    totals = dict(_ZERO_TOTALS)
    tr = trace0
    for i in range(intervals):
        if i > 0:
            tr = trace_mod.generate(app, seed, i, accesses)
        res = pol.run_interval(tr)
        totals["migrations"] += res.migrations
        totals["evictions"] += res.evictions
        totals["dirty"] += res.dirty_evictions
        totals["shootdowns"] += res.shootdowns
        totals["mig_bytes"] += res.mig_bytes
        totals["mig_cycles"] += res.mig_cycles
        totals["shootdown_cycles"] += res.shootdown_cycles
        totals["clflush_cycles"] += res.clflush_cycles
        totals["accesses"] += tr.sp.shape[0]
        totals["stall_dram"] += res.stall_dram
        totals["stall_nvm"] += res.stall_nvm
        totals["mig_stall"] += res.mig_stall
        totals["backlog_dram"] += res.backlog_dram
        totals["backlog_nvm"] += res.backlog_nvm
        totals["aborts"] += res.aborts
        totals["intervals"] += 1

    return finalize_metrics(
        app, policy, mc, totals, pol.sim.counters,
        tr.inst_per_access, tr.footprint_pages,
    )


def sweep(
    apps: list[str],
    policies: list[str],
    seeds: list[int],
    mc: MachineConfig | None = None,
    intervals: int = 5,
    accesses: int | None = None,
    counter_backend: str = "jax",
    stream: bool = False,
    journal=None,
    scenarios: list[str] = (),
    runner=None,
    timing_model: str = "flat",
    queue_geometry=None,
) -> dict[tuple[str, str, int], SimMetrics]:
    """Fleet sweep: the (app x policy x seed) grid as ONE FleetRunner plan.

    Cells sharing a compile signature are fused onto the fleet axis, sharded
    across the device mesh, and double-buffered against host trace staging
    (engine.fleet). Returns {(app, policy, seed): metrics}.

    `scenarios` adds registered workload scenarios (repro.workloads) as
    FUSED cells: their traces are synthesized inside the sharded engine scan,
    so the runner stages nothing host-side for them (apps named in `apps`,
    scenario names included, run staged).

    `stream=True` retires groups through the incremental FleetRunner.run_iter
    path and `journal` (a path) checkpoints retired groups so a killed sweep
    resumes where it stopped — both bit-identical to the barrier path.

    `runner` substitutes a configured FleetRunner (prefetch depth, compile
    cache, pipeline=False reference mode); callers can read per-group
    wall-clock breakdowns off `runner.timings` afterwards.
    """
    from repro.engine import fleet  # lazy: sim.__init__ imports this module

    plan = fleet.SweepPlan.grid(
        apps, policies, tuple(seeds), mc=mc or MachineConfig(),
        intervals=intervals, accesses=accesses,
        counter_backend=counter_backend, scenario=tuple(scenarios),
        timing_model=timing_model, queue_geometry=queue_geometry,
    )
    runner = runner or fleet.FleetRunner()
    result = runner.run(plan, stream=stream, journal=journal)
    return {(c.app, c.policy, c.seed): m for c, m in result.items()}


def workloads(include_mixes: bool = True) -> list[str]:
    w = list(APPS)
    if include_mixes:
        w += list(MIXES)
    return w
