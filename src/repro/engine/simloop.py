"""Layer-A MemoryEngine: the whole simulation as ONE device-resident lax.scan.

The eager reference path (sim.policies / sim.runner's `simulate_eager`) steps
intervals from the host: one `run_interval` dispatch + one policy-migrate
round-trip per interval. At fleet scale the control loop itself becomes the
bottleneck (cf. Nomad '24, Memos '17) — so the engine fuses the interval loop:

  EngineStep = residency -> per-access translation scan -> policy migrate
               (counting + utility admission + remap install/evict) ->
               TLB shootdowns

and `engine_run` executes `lax.scan(EngineStep)` over pre-generated trace
chunks, so a full (intervals x accesses) simulation is a single XLA program
with zero host<->device traffic inside the loop. `sweep_seeds` vmaps the same
step across seeds for fleet sweeps.

All five §IV-A policies are ported as policy-parameterized step programs:

  flat-static / dram-only : residency is state-free, precomputed per chunk
  hscc-4kb / hscc-2mb     : fixed-shape JAX ports of the HSCC utility loop
  rainbow                 : core.rainbow.interval_step (the shared controller)

The engine is bit-identical to the eager path for the state-free policies and
for rainbow (same ops, same order). The HSCC ports could in principle differ
from the old numpy reference in f32 benefit ties, but were re-validated EXACT
over the full workload table, after which the numpy host loops were deleted —
scripts/validate_hscc_parity.py regresses them against the recorded snapshot.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import rainbow as rb
from repro.core.remap import translate
from repro.engine import nomad as nomad_mod
from repro.core.tlb import SplitTLB, split_tlb_invalidate_many, tlb_invalidate
from repro.engine.policy import ControlPolicy, sim_policy_for
from repro.sim import tlbsim
from repro.sim import trace as trace_mod
from repro.sim.config import PAGES_PER_SP, MachineConfig
from repro.sim.policies import machine_timing
from repro.timing import QueueGeometry
from repro.timing import queueing as qtiming
from repro.utils import pytree_dataclass, static_field

#: TranslationKind used by the per-access scan, per policy (§IV-A table).
POLICY_KINDS = {
    "flat-static": "flat4k",
    "hscc-4kb-mig": "flat4k",
    "hscc-2mb-mig": "sp2m",
    "rainbow": "rainbow",
    "nomad": "rainbow",
    "dram-only": "sp2m",
}


@dataclasses.dataclass(frozen=True)
class TraceSource:
    """A device-resident trace program as an engine input (hashable).

    Carries the registered scenario NAME (repro.workloads.scenarios) plus the
    per-interval access-count override — everything the fused scan needs to
    synthesize each interval's chunk on device. Registration is import-time
    (the registry rejects rebinding) so a name can never alias two programs
    across the jit cache.
    """

    scenario: str
    accesses: int | None = None


@dataclasses.dataclass(frozen=True)
class EngineSpec:
    """Static configuration of one engine compile (hashable; jit static arg).

    `control` overrides the machine-derived ControlPolicy of the stateful
    policies (rainbow / HSCC ports) — the hook SweepPlan cells and the serving
    autotuner use to sweep controller knobs without touching MachineConfig.

    `source` switches the engine to FUSED trace generation: instead of
    consuming pre-staged TraceChunks, the scan body synthesizes each
    interval's chunk from the named scenario program (engine_run_fused /
    batch_run_fused take a seed where the staged entry points take chunks).
    """

    policy: str
    mc: MachineConfig
    num_superpages: int
    footprint_pages: int
    counter_backend: str = "jax"  # rainbow counting: "jax"|"ref"|"pallas"|"interpret"
    max_invalidate: int = 256  # 4KB-TLB shootdowns applied per interval (eager cap)
    control: ControlPolicy | None = None
    source: TraceSource | None = None
    # fastpath=True routes the hot path through the vectorized/fused interval
    # runner (tlbsim.make_interval_runner), batch shootdowns, and cumsum-based
    # first-k selection. fastpath=False keeps the pre-overhaul reference ops
    # (serial make_access_step scan, argsort selection, per-vpn shootdown
    # scan). Both compiles are bit-identical — the reference path exists as
    # the subprocess-isolated speedup baseline and as the differential anchor
    # for tests (tests/test_hotpath.py, tests/test_engine.py).
    fastpath: bool = True
    # timing_model="queueing" carries per-tier per-server avail_cycle clocks
    # (repro.timing) in the scan state and fills the contention fields of
    # IntervalStats; "flat" (default) keeps the event-count cost model and a
    # None queue carry. The two are bit-identical when queue_geometry is the
    # infinite flat floor (tests/test_timing.py).
    timing_model: str = "flat"
    queue_geometry: QueueGeometry | None = None

    def control_policy(self) -> ControlPolicy:
        """The effective ControlPolicy of this compile (stateful policies)."""
        return sim_policy_for(
            self.policy, self.mc, self.control, self.counter_backend
        )

    def timing_geometry(self) -> QueueGeometry | None:
        """The effective QueueGeometry (validated), or None under "flat"."""
        if self.timing_model == "flat":
            return None
        if self.timing_model != "queueing":
            raise ValueError(
                f"EngineSpec.timing_model must be 'flat' or 'queueing', "
                f"got {self.timing_model!r}"
            )
        geom = self.queue_geometry or QueueGeometry()
        geom.validate()
        return geom


class TraceChunks(NamedTuple):
    """Pre-generated device trace: [intervals, accesses] per field.

    `in_dram` carries the state-free residency of flat-static / dram-only
    (zeros for stateful policies, which derive residency on device).
    """

    sp: jax.Array  # int32[I, A]
    page: jax.Array  # int32[I, A]
    vpn: jax.Array  # int32[I, A]
    is_write: jax.Array  # bool[I, A]
    in_dram: jax.Array  # bool[I, A]


@pytree_dataclass
class HsccPolicyState:
    """DRAM residency of the HSCC ports (per 4KB page or per superpage)."""

    resident: jax.Array  # bool[num_units]
    dirty: jax.Array  # bool[num_units]
    slots_used: jax.Array  # int32 (4KB variant; the 2MB port recounts residency)


@pytree_dataclass
class EngineState:
    sim: tlbsim.SimState
    pol: Any  # policy-program state (structure is static per EngineSpec)
    q: Any = None  # timing.QueueState under timing_model="queueing"


class IntervalStats(NamedTuple):
    """Per-interval migration activity (host finalize derives bytes/cycles)
    plus the queueing model's contention metrics — f32 scalars that are
    EXACT zeros under timing_model="flat" AND under the infinite-bank floor,
    so the flat floor holds bitwise through every accumulation."""

    migrations: jax.Array  # int32
    evictions: jax.Array  # int32
    dirty_evictions: jax.Array  # int32
    shootdowns: jax.Array  # int32
    stall_dram: jax.Array  # f32: demand bank-conflict wait cycles, DRAM tier
    stall_nvm: jax.Array  # f32: demand bank-conflict wait cycles, NVM tier
    mig_stall: jax.Array  # f32: stall attributable to migration traffic
    backlog_dram: jax.Array  # f32: queue depth past interval end (cycles)
    backlog_nvm: jax.Array  # f32
    aborts: jax.Array = None  # int32: transactional migration aborts (nomad)


def _zero_stats() -> IntervalStats:
    z = jnp.zeros((), jnp.int32)
    f = jnp.zeros((), jnp.float32)
    return IntervalStats(z, z, z, z, f, f, f, f, f, z)


# ---------------------------------------------------------------------------
# Host-side trace pre-generation (outside the loop; the scan never leaves HBM)
# ---------------------------------------------------------------------------

# flat-static residency hash: (vpn * KNUTH) % MOD < MOD * dram_ratio.  The
# staged path evaluates it on int64 vpns; the fused in-scan path reduces
# KNUTH mod MOD first so the whole product fits int32 — mathematically the
# same residue, so both paths agree bit for bit.
_FLAT_HASH_KNUTH = 2654435761
_FLAT_HASH_MOD = 997


def _flat_static_threshold(mc: MachineConfig) -> int:
    return int(_FLAT_HASH_MOD * (mc.dram_bytes / (mc.dram_bytes + mc.nvm_bytes)))


def make_chunks_np(
    app: str,
    policy: str,
    mc: MachineConfig,
    seed: int,
    intervals: int,
    accesses: int | None = None,
) -> tuple[TraceChunks, dict]:
    """Generate + stack all interval traces HOST-SIDE (numpy TraceChunks).

    The fleet runner stacks many of these along a fleet axis and stages them
    to the mesh in one sharded device_put, so generation stays off-device.
    """
    if policy not in POLICY_KINDS:
        raise KeyError(
            f"unknown policy {policy!r}; expected one of {sorted(POLICY_KINDS)}"
        )
    traces = [
        trace_mod.generate(app, seed, i, accesses) for i in range(intervals)
    ]
    t0 = traces[0]
    vpn64 = np.stack([t.vpn for t in traces])
    wr = np.stack([t.is_write for t in traces])
    if policy == "flat-static":
        in_dram = (
            (vpn64 * _FLAT_HASH_KNUTH) % _FLAT_HASH_MOD
        ) < _flat_static_threshold(mc)
    elif policy == "dram-only":
        in_dram = np.ones_like(wr)
    else:
        in_dram = np.zeros_like(wr)
    chunks = TraceChunks(
        sp=np.stack([t.sp for t in traces]),
        page=np.stack([t.page for t in traces]),
        vpn=vpn64.astype(np.int32),
        is_write=wr,
        in_dram=in_dram,
    )
    meta = {
        "num_superpages": int(t0.num_superpages),
        "footprint_pages": int(t0.footprint_pages),
        "inst_per_access": float(t0.inst_per_access),
        "accesses_per_interval": int(t0.sp.shape[0]),
    }
    return chunks, meta


def make_chunks(
    app: str,
    policy: str,
    mc: MachineConfig,
    seed: int,
    intervals: int,
    accesses: int | None = None,
) -> tuple[TraceChunks, dict]:
    """Generate + stack all interval traces for one (app, policy, seed) run."""
    chunks, meta = make_chunks_np(app, policy, mc, seed, intervals, accesses)
    return jax.tree.map(jnp.asarray, chunks), meta


def require_uniform_meta(metas: list[dict], labels: list[str]) -> dict:
    """Assert every fleet member produced identical trace meta.

    Batching silently trusts member 0's shapes, so any disagreement in
    footprint / superpage count / interval length would corrupt the whole
    fleet — fail loudly, naming the offending members, instead.
    """
    keys = (
        "num_superpages", "footprint_pages",
        "accesses_per_interval", "inst_per_access",
    )
    base = metas[0]
    for lbl, m in zip(labels, metas):
        bad = [k for k in keys if m[k] != base[k]]
        if bad:
            detail = "; ".join(
                f"{k}: {labels[0]}={base[k]} vs {lbl}={m[k]}" for k in bad
            )
            raise ValueError(
                f"fleet members disagree on trace meta ({detail}) — "
                "cells with different shapes cannot share one batched compile"
            )
    return base


# ---------------------------------------------------------------------------
# Shared fixed-shape helpers
# ---------------------------------------------------------------------------


def _first_k_valid(
    values: jax.Array, valid: jax.Array, k: int, fastpath: bool = True
) -> jax.Array:
    """First k `values` whose lane is valid, in lane order; -1 padding.

    One shared implementation for engine + eager oracle (utils.select): the
    fast path is the sort-free masked-cumsum scatter, the reference the
    pre-overhaul stable argsort; tests/test_hotpath.py pins them
    bit-identical across masks and edge floors.
    """
    from repro.utils.select import first_k_valid, first_k_valid_ref

    if not fastpath:
        return first_k_valid_ref(values, valid, k)
    return first_k_valid(values, valid, k)


def _invalidate_4k(
    sim: tlbsim.SimState, vpns: jax.Array, fastpath: bool = True
) -> tlbsim.SimState:
    """Shoot down a fixed-length vpn list in the 4KB split TLB.

    -1 lanes are exact no-ops (they only rewrite already-invalid entries), so
    this matches the eager Policy._invalidate_4k host path bit for bit.

    Fast path: the shared vectorized batch shootdown
    (core.tlb.split_tlb_invalidate_many — one broadcast membership test per
    level). The reference path keeps the pre-overhaul per-vpn sequential
    scan; tests/test_hotpath.py pins the two bit-identical.
    """
    if not fastpath:

        def body(tlb4: SplitTLB, v):
            return SplitTLB(
                l1=tlb_invalidate(tlb4.l1, v), l2=tlb_invalidate(tlb4.l2, v)
            ), None

        tlb4, _ = jax.lax.scan(body, sim.tlb4, vpns)
        return sim._replace(tlb4=tlb4)

    return sim._replace(tlb4=split_tlb_invalidate_many(sim.tlb4, vpns))


def _histograms(idx: jax.Array, is_write: jax.Array, n: int, fastpath: bool = True):
    """Per-unit read/write counts as float32 histograms.

    Fast path: accumulate in int32 and convert once — scatter-adds of 0/1 in
    int32 are cheaper than float32 and the conversion is exact while per-unit
    counts stay below 2**24 (see docs/engine.md; accesses per interval are
    ~1e4-1e6, so the bound has ~16x headroom even if every access hits one
    unit). The reference path scatters float32 ones directly.
    """
    if fastpath:
        ones = jnp.ones_like(idx, dtype=jnp.int32)
        zeros = jnp.zeros_like(ones)
        reads = (
            jnp.zeros((n,), jnp.int32)
            .at[idx]
            .add(jnp.where(is_write, zeros, ones))
            .astype(jnp.float32)
        )
        writes = (
            jnp.zeros((n,), jnp.int32)
            .at[idx]
            .add(jnp.where(is_write, ones, zeros))
            .astype(jnp.float32)
        )
        return reads, writes
    reads = jnp.zeros((n,), jnp.float32).at[idx].add(
        jnp.where(is_write, 0.0, 1.0)
    )
    writes = jnp.zeros((n,), jnp.float32).at[idx].add(
        jnp.where(is_write, 1.0, 0.0)
    )
    return reads, writes


# ---------------------------------------------------------------------------
# Policy programs: init / residency / migrate
# ---------------------------------------------------------------------------


def _rainbow_cfg(spec: EngineSpec) -> rb.RainbowConfig:
    return rb.RainbowConfig(
        num_superpages=spec.num_superpages,
        pages_per_sp=PAGES_PER_SP,
        policy=spec.control_policy(),
    )


def engine_init(spec: EngineSpec) -> EngineState:
    sim = tlbsim.init_state(spec.mc)
    if spec.policy == "rainbow":
        # threshold comes from the policy's threshold_init (mc.mig_threshold
        # for the default preset; an EngineSpec.control override wins)
        pol: Any = rb.rainbow_init(_rainbow_cfg(spec))
    elif spec.policy == "nomad":
        pol = nomad_mod.nomad_init(_rainbow_cfg(spec))
    elif spec.policy == "hscc-4kb-mig":
        pol = HsccPolicyState(
            resident=jnp.zeros((spec.footprint_pages,), bool),
            dirty=jnp.zeros((spec.footprint_pages,), bool),
            slots_used=jnp.zeros((), jnp.int32),
        )
    elif spec.policy == "hscc-2mb-mig":
        pol = HsccPolicyState(
            resident=jnp.zeros((spec.num_superpages,), bool),
            dirty=jnp.zeros((spec.num_superpages,), bool),
            slots_used=jnp.zeros((), jnp.int32),
        )
    else:  # flat-static / dram-only: state-free
        pol = None
    geom = spec.timing_geometry()
    q = qtiming.queue_init(geom) if geom is not None else None
    return EngineState(sim=sim, pol=pol, q=q)


def _rainbow_finish(spec: EngineSpec, rep) -> tuple[IntervalStats, jax.Array]:
    """Shootdown list + interval stats from a rainbow IntervalReport."""
    # NVM->DRAM migration needs NO shootdown (superpage mapping unchanged);
    # only DRAM->NVM writeback shoots down the 4KB entries (paper §III-F).
    ev_valid = rep.plan.evict_sp >= 0
    ev_vpn = rep.plan.evict_sp * PAGES_PER_SP + rep.plan.evict_page
    inval = _first_k_valid(ev_vpn, ev_valid, spec.max_invalidate, spec.fastpath)
    stats = _zero_stats()._replace(
        migrations=rep.n_migrated,
        evictions=rep.n_evicted,
        dirty_evictions=rep.n_dirty_evicted,
        shootdowns=rep.n_evicted,
    )
    return stats, inval


def _rainbow_migrate(spec: EngineSpec, pol, chunk):
    cfg = _rainbow_cfg(spec)
    pol, rep = rb.interval_step(
        cfg, pol, chunk.sp, chunk.page, chunk.is_write, machine_timing(spec.mc)
    )
    with jax.named_scope("apply"):
        stats, inval = _rainbow_finish(spec, rep)
    return pol, stats, inval


def _nomad_finish(spec: EngineSpec, rep) -> tuple[IntervalStats, jax.Array]:
    """Shootdown list + interval stats from a NomadReport.

    Aborted pages move back to NVM, so their 4KB entries are shot down like
    evictions (aborts first: they were rolled back before the plan ran).
    With async_window == 1 (or aborts disabled) rep.abort_vpn is None and
    this reduces STATICALLY to _rainbow_finish — the degenerate gate's
    bitwise anchor.
    """
    r = rep.rb
    ev_valid = r.plan.evict_sp >= 0
    ev_vpn = r.plan.evict_sp * PAGES_PER_SP + r.plan.evict_page
    if rep.abort_vpn is not None:
        vals = jnp.concatenate([rep.abort_vpn, ev_vpn])
        valid = jnp.concatenate([rep.abort_vpn >= 0, ev_valid])
    else:
        vals, valid = ev_vpn, ev_valid
    inval = _first_k_valid(vals, valid, spec.max_invalidate, spec.fastpath)
    stats = _zero_stats()._replace(
        migrations=r.n_migrated,
        evictions=r.n_evicted,
        dirty_evictions=r.n_dirty_evicted,
        shootdowns=r.n_evicted + rep.n_aborts,
        aborts=rep.n_aborts,
    )
    return stats, inval


def _nomad_migrate(spec: EngineSpec, pol, chunk):
    """pol', stats, shootdowns, (bulk_dram, bulk_nvm) — the bulk pair is the
    interval's installment for the queueing model's bulk_charge."""
    cfg = _rainbow_cfg(spec)
    pol, rep = nomad_mod.nomad_interval(
        cfg, pol, chunk.sp, chunk.page, chunk.is_write,
        machine_timing(spec.mc), spec.mc,
    )
    with jax.named_scope("plan"):
        stats, inval = _nomad_finish(spec, rep)
    return pol, stats, inval, (rep.bulk_dram, rep.bulk_nvm)


def _hscc_admit(
    mc: MachineConfig,
    resident: jax.Array,
    dirty: jax.Array,
    reads: jax.Array,
    writes: jax.Array,
    free: jax.Array,
    cand_k: int,
    unit_mig_cost: float,
    unit_writeback: float,
    threshold: float,
):
    """Fixed-shape HSCC admission: free slots best-first, then swap vs coldest.

    Faithful port of the numpy Hscc4K/Hscc2M.migrate reference (validated
    exact over the full workload table, then deleted — see
    scripts/validate_hscc_parity.py): candidates are the top-`cand_k`
    non-resident units by Eq. 1 benefit above the threshold; the first `free`
    fill free slots, the rest are paired rank-for-rank with the coldest
    residents and admitted when the (double-counted, as in the reference)
    swap gain clears the threshold.
    """
    n = resident.shape[0]
    benefit = (
        (mc.t_nr - mc.t_dr) * reads + (mc.t_nw - mc.t_dw) * writes - unit_mig_cost
    )
    benefit = jnp.where(resident, -jnp.inf, benefit)
    k = min(cand_k, n)
    b_top, cand = jax.lax.top_k(benefit, k)
    ok = b_top > threshold

    rank = jnp.cumsum(ok.astype(jnp.int32)) - 1  # rank among admitted lanes
    admit_free = ok & (rank < free)
    resident = resident.at[jnp.where(admit_free, cand, n)].set(True, mode="drop")
    n_free = admit_free.sum().astype(jnp.int32)

    # Swap path: pair overflow candidates with the coldest residents
    # (residency measured after the free admissions, as in the reference).
    rest = ok & (rank >= free)
    rrank = jnp.clip(rank - free, 0, k - 1)
    hotness = reads + writes
    cold_score = jnp.where(resident, hotness, jnp.inf)
    _, victims = jax.lax.top_k(-cold_score, k)
    vic = victims[rrank]
    vic_ok = resident[vic] & rest
    gain_out = (mc.t_nr - mc.t_dr) * reads[vic] + (mc.t_nw - mc.t_dw) * writes[vic]
    wb = jnp.where(dirty[vic], unit_writeback, 0.0)
    ok2 = vic_ok & (b_top - gain_out - unit_mig_cost - wb > threshold)

    resident = resident.at[jnp.where(ok2, vic, n)].set(False, mode="drop")
    resident = resident.at[jnp.where(ok2, cand, n)].set(True, mode="drop")
    dirty_ev = (ok2 & dirty[vic]).sum().astype(jnp.int32)
    dirty = dirty.at[jnp.where(ok2, vic, n)].set(False, mode="drop")

    n_swap = ok2.sum().astype(jnp.int32)
    stats = _zero_stats()._replace(
        migrations=n_free + n_swap,
        evictions=n_swap,
        dirty_evictions=dirty_ev,
        shootdowns=n_free + 2 * n_swap,
    )
    return resident, dirty, n_free, stats, cand, ok


def _hscc4k_migrate(spec: EngineSpec, pol: HsccPolicyState, chunk):
    mc, fp = spec.mc, spec.footprint_pages
    cpol = spec.control_policy()  # "hscc-4kb" preset unless overridden
    vpn = jnp.minimum(chunk.vpn, fp - 1)
    reads, writes = _histograms(vpn, chunk.is_write, fp, spec.fastpath)
    dirty = pol.dirty | (pol.resident & (writes > 0))
    free = jnp.maximum(cpol.hot_slots - pol.slots_used, 0)
    resident, dirty, n_free, stats, cand, ok = _hscc_admit(
        mc, pol.resident, dirty, reads, writes, free,
        cand_k=cpol.max_promotions, unit_mig_cost=mc.mig_page_cost,
        unit_writeback=mc.writeback_page_cost,
        threshold=cpol.threshold_init,
    )
    pol = HsccPolicyState(
        resident=resident, dirty=dirty, slots_used=pol.slots_used + n_free
    )
    inval = _first_k_valid(cand, ok, 64, spec.fastpath)  # eager: _invalidate_4k(cand[:64])
    return pol, stats, inval


def _hscc2m_migrate(spec: EngineSpec, pol: HsccPolicyState, chunk):
    mc, nsp = spec.mc, spec.num_superpages
    cpol = spec.control_policy()  # "hscc-2mb" preset unless overridden
    reads, writes = _histograms(chunk.sp, chunk.is_write, nsp, spec.fastpath)
    dirty = pol.dirty | (pol.resident & (writes > 0))
    free = jnp.maximum(cpol.hot_slots - pol.resident.sum().astype(jnp.int32), 0)
    resident, dirty, _, stats, _, _ = _hscc_admit(
        mc, pol.resident, dirty, reads, writes, free,
        cand_k=cpol.max_promotions, unit_mig_cost=mc.mig_page_cost * PAGES_PER_SP,
        unit_writeback=mc.writeback_page_cost * PAGES_PER_SP,
        threshold=cpol.threshold_init,
    )
    return HsccPolicyState(resident=resident, dirty=dirty, slots_used=pol.slots_used), stats, None


# ---------------------------------------------------------------------------
# EngineStep + scanned run
# ---------------------------------------------------------------------------


def _residency(
    spec: EngineSpec, state: EngineState, chunk: TraceChunks
) -> jax.Array:
    """Per-access fast-tier residency at interval start (policy-specific)."""
    if spec.policy == "rainbow":
        in_dram, _ = translate(state.pol.remap, chunk.sp, chunk.page)
    elif spec.policy == "nomad":
        in_dram = nomad_mod.residency(
            _rainbow_cfg(spec), state.pol, chunk.sp, chunk.page, chunk.is_write
        )
    elif spec.policy == "hscc-4kb-mig":
        in_dram = state.pol.resident[
            jnp.minimum(chunk.vpn, spec.footprint_pages - 1)
        ]
    elif spec.policy == "hscc-2mb-mig":
        in_dram = state.pol.resident[chunk.sp]
    else:
        in_dram = chunk.in_dram
    return in_dram


def _access_scan(
    spec: EngineSpec, sim: tlbsim.SimState, chunk: TraceChunks, in_dram: jax.Array
) -> tlbsim.SimState:
    """The per-access translation walk (fast interval runner or reference scan)."""
    if spec.fastpath:
        run = tlbsim.make_interval_runner(POLICY_KINDS[spec.policy], spec.mc)
        return run(sim, chunk.vpn, chunk.sp, in_dram, chunk.is_write)
    step = tlbsim.make_access_step(POLICY_KINDS[spec.policy], spec.mc)
    sim, _ = jax.lax.scan(
        step, sim, (chunk.vpn, chunk.sp, in_dram, chunk.is_write)
    )
    return sim


def engine_step(
    spec: EngineSpec, state: EngineState, chunk: TraceChunks
) -> tuple[EngineState, IntervalStats]:
    """One interval, device-resident: residency -> access scan -> migrate.

    Each phase runs under the `jax.named_scope` that engine.profile names
    its phase program after (tlb, observe, plan, apply, queue), so the ops
    of the fused program carry the phase in their op_name metadata.
    """
    policy = spec.policy
    with jax.named_scope("tlb"):
        in_dram = _residency(spec, state, chunk)
        t0 = state.sim.t  # access clock BEFORE this interval's walk
        sim = _access_scan(spec, state.sim, chunk, in_dram)

    inval = None
    bulk = None
    if policy == "rainbow":
        pol, stats, inval = _rainbow_migrate(spec, state.pol, chunk)
    elif policy == "nomad":
        pol, stats, inval, bulk = _nomad_migrate(spec, state.pol, chunk)
    elif policy == "hscc-4kb-mig":
        with jax.named_scope("plan"):
            pol, stats, inval = _hscc4k_migrate(spec, state.pol, chunk)
    elif policy == "hscc-2mb-mig":
        with jax.named_scope("plan"):
            pol, stats, _ = _hscc2m_migrate(spec, state.pol, chunk)
    else:
        pol, stats = state.pol, _zero_stats()
    if inval is not None:
        with jax.named_scope("apply"):
            sim = _invalidate_4k(sim, inval, spec.fastpath)
    q = state.q
    geom = spec.timing_geometry()
    if geom is not None:
        extra = {} if bulk is None else {
            "bulk_dram": bulk[0], "bulk_nvm": bulk[1],
        }
        with jax.named_scope("queue"):
            q, tm = qtiming.interval_step(
                geom, spec.mc, policy, state.q,
                chunk.vpn, chunk.is_write, in_dram, t0,
                stats.migrations, stats.evictions, stats.dirty_evictions,
                **extra,
            )
        stats = stats._replace(
            stall_dram=tm.stall_dram,
            stall_nvm=tm.stall_nvm,
            mig_stall=tm.mig_stall,
            backlog_dram=tm.backlog_dram,
            backlog_nvm=tm.backlog_nvm,
        )
    return EngineState(sim=sim, pol=pol, q=q), stats


@functools.partial(jax.jit, static_argnames=("spec",))
def _engine_run_jit(
    spec: EngineSpec, state: EngineState, chunks: TraceChunks
) -> tuple[EngineState, IntervalStats]:
    return jax.lax.scan(
        lambda st, ch: engine_step(spec, st, ch), state, chunks
    )


@functools.partial(jax.jit, static_argnames=("spec",), donate_argnums=(1,))
def _engine_run_donated(
    spec: EngineSpec, state: EngineState, chunks: TraceChunks
) -> tuple[EngineState, IntervalStats]:
    return jax.lax.scan(
        lambda st, ch: engine_step(spec, st, ch), state, chunks
    )


def _dealias(state):
    """Copy leaves that repeat a buffer, so the pytree is safe to donate.

    Init helpers legitimately reuse one device array across fields
    (zero_counters' 14 scalars, dram_init's zeros) — XLA rejects donating
    the same buffer twice, so duplicates get a one-off copy here. First
    occurrence keeps the original buffer and still donates in place.
    """
    seen: set[int] = set()

    def one(x):
        if isinstance(x, jax.Array):
            if id(x) in seen:
                return jnp.array(x)
            seen.add(id(x))
        return x

    return jax.tree.map(one, state)


def engine_run(
    spec: EngineSpec,
    state: EngineState,
    chunks: TraceChunks,
    *,
    donate: bool = False,
    profile: bool = False,
):
    """The whole simulation as one lax.scan over interval chunks.

    donate=True donates the input EngineState's buffers to the scan carry
    (the caller must not reuse `state` afterwards — sim.runner.simulate
    qualifies, benchmarks that re-run from one state0 do not).

    profile=True instead drives the intervals from the host through
    phase-split compiles and returns (state, stats, EngineProfile) — same
    ops in the same order, so the results are bit-identical to the scanned
    run (asserted in tests/test_hotpath.py); see engine.profile.
    """
    if profile:
        from repro.engine.profile import run_profiled

        return run_profiled(spec, state, chunks)
    if donate:
        return _engine_run_donated(spec, _dealias(state), chunks)
    return _engine_run_jit(spec, state, chunks)


@functools.lru_cache(maxsize=None)
def batch_run(spec: EngineSpec):
    """Unjitted whole-sim runner vmapped over a leading fleet axis.

    The single body shared by `engine_run_batch` (one-device vmap) and
    `engine.fleet`'s shard_map partitions — so the sharded fleet is the same
    program per shard, bit for bit, as the PR 1 vmap path.

    Memoized per spec: callers wrap the body in jit/shard_map, whose tracing
    caches key on function identity — a fresh closure per call would retrace
    every group dispatch even when the compile signature repeats. (Entries
    are closures, a few hundred bytes per distinct spec.)
    """

    def run(states: EngineState, chunks: TraceChunks):
        return jax.vmap(
            lambda st, ch: jax.lax.scan(
                lambda s, c: engine_step(spec, s, c), st, ch
            )
        )(states, chunks)

    return run


@functools.partial(jax.jit, static_argnames=("spec",))
def engine_run_batch(
    spec: EngineSpec, states: EngineState, chunks: TraceChunks
) -> tuple[EngineState, IntervalStats]:
    """vmap of engine_run over a leading batch dim (fleet sweeps over seeds)."""
    return batch_run(spec)(states, chunks)


# ---------------------------------------------------------------------------
# Fused in-scan trace generation (EngineSpec.source)
# ---------------------------------------------------------------------------


def _fused_program(spec: EngineSpec):
    """(setup, emit) of the spec's scenario, shape-checked against the spec.

    Raises loudly when the spec is staged or the scenario's static shapes
    disagree with the compile signature — a fused cell must never silently
    fall back to (or group with) a different shape than it emits.
    """
    from repro.workloads import scenarios  # lazy: workloads -> sim.config

    if spec.source is None:
        raise ValueError(
            "EngineSpec.source is None: this is a staged compile — feed it "
            "TraceChunks via engine_run/engine_run_batch, or set source="
            "TraceSource(scenario, accesses) for fused in-scan generation"
        )
    setup, emit, meta = scenarios.trace_program(
        spec.source.scenario, spec.source.accesses
    )
    if (meta["num_superpages"] != spec.num_superpages
            or meta["footprint_pages"] != spec.footprint_pages):
        raise ValueError(
            f"EngineSpec/{spec.source.scenario!r} shape mismatch: spec has "
            f"(num_superpages={spec.num_superpages}, footprint_pages="
            f"{spec.footprint_pages}) but the scenario program emits "
            f"(num_superpages={meta['num_superpages']}, footprint_pages="
            f"{meta['footprint_pages']})"
        )
    return setup, emit


def synth_chunk(spec: EngineSpec, emit, aux, seed, interval) -> TraceChunks:
    """One interval's TraceChunks synthesized on device (inside the scan).

    Field-for-field what make_chunks_np stages for the same workload: vpn is
    the emitted page index, sp/page its superpage split, and `in_dram`
    carries the state-free residency of flat-static / dram-only.
    """
    vpn, is_write = emit(aux, seed, interval)
    if spec.policy == "flat-static":
        in_dram = (
            (vpn % _FLAT_HASH_MOD) * (_FLAT_HASH_KNUTH % _FLAT_HASH_MOD)
            % _FLAT_HASH_MOD
        ) < _flat_static_threshold(spec.mc)
    elif spec.policy == "dram-only":
        in_dram = jnp.ones_like(is_write)
    else:
        in_dram = jnp.zeros_like(is_write)
    return TraceChunks(
        sp=vpn // PAGES_PER_SP,
        page=vpn % PAGES_PER_SP,
        vpn=vpn,
        is_write=is_write,
        in_dram=in_dram,
    )


def _fused_scan(
    spec: EngineSpec, state: EngineState, seed, intervals: int
) -> tuple[EngineState, IntervalStats]:
    """The whole simulation as one lax.scan, chunks synthesized in the body.

    The scenario's seed-dependent setup (e.g. hot-page placement) runs ONCE,
    outside the scan; each scan step folds the interval index into the seed's
    key stream and emits that interval's chunk right where engine_step
    consumes it — zero staging, zero host<->device trace traffic.
    """
    setup, emit = _fused_program(spec)
    seed = jnp.asarray(seed, jnp.int32)
    with jax.named_scope("synth"):
        aux = setup(seed)

    def body(st, i):
        with jax.named_scope("synth"):
            chunk = synth_chunk(spec, emit, aux, seed, i)
        return engine_step(spec, st, chunk)

    return jax.lax.scan(body, state, jnp.arange(intervals, dtype=jnp.int32))


@functools.partial(jax.jit, static_argnames=("spec", "intervals"))
def _engine_run_fused_jit(
    spec: EngineSpec, state: EngineState, seed, intervals: int
) -> tuple[EngineState, IntervalStats]:
    return _fused_scan(spec, state, seed, intervals)


@functools.partial(
    jax.jit, static_argnames=("spec", "intervals"), donate_argnums=(1,)
)
def _engine_run_fused_donated(
    spec: EngineSpec, state: EngineState, seed, intervals: int
) -> tuple[EngineState, IntervalStats]:
    return _fused_scan(spec, state, seed, intervals)


def engine_run_fused(
    spec: EngineSpec,
    state: EngineState,
    seed,
    intervals: int,
    *,
    donate: bool = False,
    profile: bool = False,
):
    """Fused counterpart of engine_run: a seed in, a full simulation out.

    donate/profile behave as in engine_run (the profiled run synthesizes each
    interval's chunk host-driven via the same scenario program and reports it
    as a separate "synth" phase).
    """
    if profile:
        from repro.engine.profile import run_profiled

        return run_profiled(spec, state, None, seed=seed, intervals=intervals)
    if donate:
        return _engine_run_fused_donated(spec, _dealias(state), seed, intervals)
    return _engine_run_fused_jit(spec, state, seed, intervals)


@functools.lru_cache(maxsize=None)
def batch_run_fused(spec: EngineSpec, intervals: int):
    """Unjitted fused whole-sim runner vmapped over a leading fleet axis.

    The single body shared by `engine_run_fused_batch` (one-device vmap) and
    `engine.fleet`'s fused shard_map partitions — same program per shard,
    bit for bit, as the single-device fused path.

    Memoized per (spec, intervals) so repeated group dispatches reuse one
    function identity (see batch_run).
    """
    _fused_program(spec)  # staged/mismatched specs fail HERE, not at trace

    def run(states: EngineState, seeds):
        return jax.vmap(
            lambda st, sd: _fused_scan(spec, st, sd, intervals)
        )(states, seeds)

    return run


@functools.partial(jax.jit, static_argnames=("spec", "intervals"))
def engine_run_fused_batch(
    spec: EngineSpec, states: EngineState, seeds, intervals: int
) -> tuple[EngineState, IntervalStats]:
    """vmap of engine_run_fused over a seed fleet (one batched compile)."""
    return batch_run_fused(spec, intervals)(states, seeds)


def sweep_seeds(
    app: str,
    policy: str,
    mc: MachineConfig,
    seeds: list[int],
    intervals: int = 5,
    accesses: int | None = None,
    counter_backend: str = "jax",
    timing_model: str = "flat",
    queue_geometry=None,
) -> tuple[EngineState, IntervalStats, dict]:
    """Run one (app, policy) across a seed fleet in a single batched compile.

    Returns (final states, per-interval stats [S, I], meta). Apps/policies
    change array shapes and scan structure, so the host shell loops over them
    and vmaps the homogeneous axis (seeds) here.
    """
    chunk_list, meta = zip(
        *(make_chunks(app, policy, mc, s, intervals, accesses) for s in seeds)
    )
    chunks = jax.tree.map(lambda *xs: jnp.stack(xs), *chunk_list)
    meta0 = require_uniform_meta(list(meta), [f"seed={s}" for s in seeds])
    spec = EngineSpec(
        policy=policy,
        mc=mc,
        num_superpages=meta0["num_superpages"],
        footprint_pages=meta0["footprint_pages"],
        counter_backend=counter_backend,
        timing_model=timing_model,
        queue_geometry=queue_geometry,
    )
    state0 = engine_init(spec)
    states = jax.tree.map(
        lambda x: jnp.stack([x] * len(seeds)), state0
    )
    finals, stats = engine_run_batch(spec, states, chunks)
    return finals, stats, meta0
