"""Utility-based hot-page migration (Rainbow §III-C) + DRAM list management.

Implements Eq. 1 / Eq. 2 of the paper, the adaptive migration-benefit threshold, and
the free/clean/dirty DRAM slot manager (HSCC-style three lists, realized here as a
per-slot state array with LRU ordering inside each class — functionally equivalent
and fully vectorizable).

All functions are pure; the controller state threads through jit/scan.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.utils import pytree_dataclass, static_field

FREE, CLEAN, DIRTY = 0, 1, 2


@pytree_dataclass
class TimingParams:
    """Table III parameters (cycles)."""

    t_nr: jax.Array  # NVM read latency
    t_nw: jax.Array  # NVM write latency
    t_dr: jax.Array  # DRAM read latency
    t_dw: jax.Array  # DRAM write latency
    t_mig: jax.Array  # cycles to migrate one page NVM -> DRAM
    t_writeback: jax.Array  # cycles to write a dirty DRAM page back to NVM


def _check_latency(name: str, value, *, positive: bool) -> None:
    """Reject malformed timing constants loudly at construction.

    Only concrete host scalars are checked (traced values pass through —
    every production caller builds TimingParams from python floats, outside
    any trace).
    """
    try:
        v = float(value)
    except (TypeError, ValueError):
        raise ValueError(
            f"timing parameter {name} must be a real number, got {value!r}"
        ) from None
    if v != v or v in (float("inf"), float("-inf")):
        raise ValueError(f"timing parameter {name} must be finite, got {v!r}")
    if positive and v <= 0:
        raise ValueError(
            f"timing parameter {name} must be positive, got {v!r}"
        )
    if not positive and v < 0:
        raise ValueError(
            f"timing parameter {name} must be non-negative, got {v!r}"
        )


def make_timing(
    t_nr: float, t_nw: float, t_dr: float, t_dw: float, t_mig: float, t_writeback: float
) -> TimingParams:
    for name, value in (("t_nr", t_nr), ("t_nw", t_nw),
                        ("t_dr", t_dr), ("t_dw", t_dw)):
        if not isinstance(value, jax.core.Tracer):
            _check_latency(name, value, positive=True)
    for name, value in (("t_mig", t_mig), ("t_writeback", t_writeback)):
        if not isinstance(value, jax.core.Tracer):
            _check_latency(name, value, positive=False)
    f = lambda x: jnp.asarray(x, jnp.float32)
    return TimingParams(f(t_nr), f(t_nw), f(t_dr), f(t_dw), f(t_mig), f(t_writeback))


# -- named hardware presets (THE single source of both timing tables) --------

#: Paper Table IV machine constants. sim.config re-exports these (CPU_GHZ /
#: PAGE_BYTES) so the clock and page size that build the preset latencies are
#: the same values the rest of the machine model derives from.
SIM_CPU_GHZ = 3.2  # cycles = ns * GHz
SIM_PAGE_BYTES = 4096
_SIM_PAGE_COST = (
    (SIM_PAGE_BYTES / 10.7e9) * 1e9 * SIM_CPU_GHZ * 2  # rd PCM + wr DRAM
)

#: Every hand-maintained latency table lives HERE, once. "paper-table4-sim" is
#: the simulator's machine model (cycles @ 3.2 GHz; MachineConfig's latency
#: defaults read these entries). "v5e-serving" is the serving cost model in
#: ns-per-block units (819 GB/s HBM vs ~50 GB/s host link; t_mig = one block
#: DMA + setup), consumed by memory.kvcache and engine.autotune.
TIMING_PRESETS: dict[str, dict[str, float]] = {
    "paper-table4-sim": {
        "t_nr": 19.5 * SIM_CPU_GHZ,  # PCM read   = 62.4
        "t_nw": 171.0 * SIM_CPU_GHZ,  # PCM write  = 547.2
        "t_dr": 13.5 * SIM_CPU_GHZ,  # DRAM read  = 43.2
        "t_dw": 28.5 * SIM_CPU_GHZ,  # DRAM write = 91.2
        "t_mig": _SIM_PAGE_COST,
        "t_writeback": _SIM_PAGE_COST,
    },
    "v5e-serving": {
        "t_nr": 100.0,
        "t_nw": 180.0,
        "t_dr": 8.0,
        "t_dw": 12.0,
        "t_mig": 400.0,
        "t_writeback": 400.0,
    },
}


_PRESET_KEYS = frozenset(
    {"t_nr", "t_nw", "t_dr", "t_dw", "t_mig", "t_writeback"}
)


def _validate_preset(name: str, entry) -> None:
    """Malformed preset dicts fail HERE with the preset named, not deep in
    the cost model (a bad entry used to flow silently into every latency)."""
    if not isinstance(entry, dict):
        raise ValueError(
            f"timing preset {name!r} must be a dict, got {type(entry).__name__}"
        )
    got = set(entry)
    if got != _PRESET_KEYS:
        missing, extra = sorted(_PRESET_KEYS - got), sorted(got - _PRESET_KEYS)
        raise ValueError(
            f"timing preset {name!r} has malformed keys "
            f"(missing={missing}, unexpected={extra})"
        )
    for key in ("t_nr", "t_nw", "t_dr", "t_dw"):
        _check_latency(f"{name}.{key}", entry[key], positive=True)
    for key in ("t_mig", "t_writeback"):
        _check_latency(f"{name}.{key}", entry[key], positive=False)


def preset_timing(name: str) -> TimingParams:
    """TimingParams for a named hardware preset (see TIMING_PRESETS)."""
    try:
        entry = TIMING_PRESETS[name]
    except KeyError:
        raise KeyError(
            f"unknown timing preset {name!r}; "
            f"available: {sorted(TIMING_PRESETS)}"
        ) from None
    _validate_preset(name, entry)
    return make_timing(**entry)


for _name, _entry in TIMING_PRESETS.items():  # built-ins checked at import
    _validate_preset(_name, _entry)
del _name, _entry


def migration_benefit(c_r: jax.Array, c_w: jax.Array, t: TimingParams) -> jax.Array:
    """Eq. 1: cycles saved by serving (C_r, C_w) from DRAM instead of NVM."""
    return (t.t_nr - t.t_dr) * c_r + (t.t_nw - t.t_dw) * c_w - t.t_mig


def swap_benefit(
    c_r_in: jax.Array,
    c_w_in: jax.Array,
    c_r_out: jax.Array,
    c_w_out: jax.Array,
    t: TimingParams,
    victim_dirty: jax.Array,
) -> jax.Array:
    """Eq. 2: benefit when migrating page p2 in requires evicting DRAM page p1.

    T_writeback applies only when the victim is dirty (clean evictions write back
    just the 8-byte remap pointer — §III-E — which we fold into T_mig noise).
    """
    wb = jnp.where(victim_dirty, t.t_writeback, 0.0)
    return (
        (t.t_nr - t.t_dr) * (c_r_in - c_r_out)
        + (t.t_nw - t.t_dw) * (c_w_in - c_w_out)
        - t.t_mig
        - wb
    )


@pytree_dataclass
class DramState:
    """Performance-tier slot manager (free/clean/dirty lists as a state array).

    slot_state:  int32[S] in {FREE, CLEAN, DIRTY}
    slot_sp:     int32[S] superpage of the cached page (-1 if free)
    slot_page:   int32[S] small-page index within that superpage
    slot_reads:  float32[S] accesses observed this interval (for Eq. 2 victims)
    slot_writes: float32[S]
    last_touch:  int32[S] LRU timestamp within class
    """

    slot_state: jax.Array
    slot_sp: jax.Array
    slot_page: jax.Array
    slot_reads: jax.Array
    slot_writes: jax.Array
    last_touch: jax.Array


def dram_init(num_slots: int) -> DramState:
    # every field its own buffer, so a jitted step may donate the state
    return DramState(
        slot_state=jnp.zeros((num_slots,), jnp.int32),
        slot_sp=jnp.full((num_slots,), -1, jnp.int32),
        slot_page=jnp.full((num_slots,), -1, jnp.int32),
        slot_reads=jnp.zeros((num_slots,), jnp.float32),
        slot_writes=jnp.zeros((num_slots,), jnp.float32),
        last_touch=jnp.zeros((num_slots,), jnp.int32),
    )


def dram_record_access(
    d: DramState, slot: jax.Array, is_write: jax.Array, now: jax.Array
) -> DramState:
    """Record a batch of DRAM-tier accesses (slot < 0 lanes ignored)."""
    valid = slot >= 0
    s = jnp.where(valid, slot, 0)
    r_inc = jnp.where(valid & ~is_write, 1.0, 0.0)
    w_inc = jnp.where(valid & is_write, 1.0, 0.0)
    reads = d.slot_reads.at[s].add(r_inc)
    writes = d.slot_writes.at[s].add(w_inc)
    state = d.slot_state.at[s].max(jnp.where(valid & is_write, DIRTY, 0))
    touch = d.last_touch.at[s].max(jnp.where(valid, now, 0))
    return DramState(
        slot_state=state,
        slot_sp=d.slot_sp,
        slot_page=d.slot_page,
        slot_reads=reads,
        slot_writes=writes,
        last_touch=touch,
    )


@pytree_dataclass
class MigrationPlan:
    """Output of plan_migrations — aligned arrays of length K (num candidates).

    migrate:   bool[K]   candidate admitted
    dst_slot:  int32[K]  destination performance-tier slot (-1 if not migrated)
    evict_sp / evict_page: int32[K] previous occupant (-1 if the slot was free)
    evict_dirty: bool[K] previous occupant needs full writeback
    benefit:   float32[K] adjusted benefit used for the decision
    """

    migrate: jax.Array
    dst_slot: jax.Array
    evict_sp: jax.Array
    evict_page: jax.Array
    evict_dirty: jax.Array
    benefit: jax.Array


def plan_migrations(
    cand_sp: jax.Array,  # int32[K] candidate superpage ids (-1 = empty lane)
    cand_page: jax.Array,  # int32[K]
    cand_reads: jax.Array,  # float32[K] predicted next-interval reads (history)
    cand_writes: jax.Array,  # float32[K]
    dram: DramState,
    timing: TimingParams,
    threshold: jax.Array,
) -> MigrationPlan:
    """Admit candidates best-first into victims cheapest-first (free→clean→dirty).

    Mirrors the paper's policy: free and clean slots are consumed before any dirty
    eviction; within a class, victims are LRU. Candidate order is by Eq. 1 benefit
    descending so the hottest pages land on the cheapest slots.
    """
    k = cand_sp.shape[0]
    base_benefit = migration_benefit(cand_reads, cand_writes, timing)
    base_benefit = jnp.where(cand_sp >= 0, base_benefit, -jnp.inf)
    # Descending benefit via top_k over the full lane set: identical order to
    # the former stable argsort(-base_benefit) (top_k breaks ties lower-index
    # first, exactly like a stable ascending sort of the negation) and hands
    # back the sorted benefits for free, saving the post-sort gather.
    c_base, cand_order = jax.lax.top_k(base_benefit, k)

    # Victim preference: class priority then LRU. Exclude slots already caching a
    # candidate (cannot evict what we are about to install — caller dedupes).
    prio = dram.slot_state.astype(jnp.float32) * 1e9 + dram.last_touch.astype(
        jnp.float32
    )
    n_slots = dram.slot_state.shape[0]

    take = min(k, n_slots)
    # Partial selection: only the `take` cheapest victims are ever paired with
    # a candidate column, so top_k(-prio, take) replaces the full slot argsort
    # (prio >= 0, so the negation is exact; tie-break matches stable argsort).
    _, victim_idx = jax.lax.top_k(-prio, take)
    vslots = victim_idx.astype(jnp.int32)
    if k > take:  # pad victim columns up to k with -1 (static shapes)
        vslots = jnp.concatenate([vslots, jnp.full((k - take,), -1, jnp.int32)])

    v_valid = vslots >= 0
    vs = jnp.where(v_valid, vslots, 0)
    v_state = jnp.where(v_valid, dram.slot_state[vs], DIRTY)
    v_sp = jnp.where(v_valid, dram.slot_sp[vs], -1)
    v_page = jnp.where(v_valid, dram.slot_page[vs], -1)
    v_reads = jnp.where(v_valid, dram.slot_reads[vs], jnp.inf)
    v_writes = jnp.where(v_valid, dram.slot_writes[vs], jnp.inf)
    v_dirty = v_state == DIRTY
    v_free = v_state == FREE

    c_sp = cand_sp[cand_order]
    c_page = cand_page[cand_order]
    c_r = cand_reads[cand_order]
    c_w = cand_writes[cand_order]

    # Adjusted benefit: Eq. 1 into free slots, Eq. 2 against occupied victims.
    adj = jnp.where(
        v_free,
        c_base,
        swap_benefit(c_r, c_w, v_reads, v_writes, timing, v_dirty),
    )
    migrate = (adj > threshold) & (c_sp >= 0) & v_valid

    plan_sorted = MigrationPlan(
        migrate=migrate,
        dst_slot=jnp.where(migrate, vslots, -1),
        evict_sp=jnp.where(migrate & ~v_free, v_sp, -1),
        evict_page=jnp.where(migrate & ~v_free, v_page, -1),
        evict_dirty=migrate & ~v_free & v_dirty,
        benefit=adj,
    )
    # Un-sort back to caller's candidate order: the inverse of a permutation
    # is a conflict-free scatter (inv[order[i]] = i), no second sort needed.
    inv = (
        jnp.zeros((k,), cand_order.dtype)
        .at[cand_order]
        .set(jnp.arange(k, dtype=cand_order.dtype))
    )
    return jax.tree.map(lambda a: a[inv], plan_sorted)


def dram_apply_plan(
    d: DramState, plan: MigrationPlan, cand_sp: jax.Array, cand_page: jax.Array, now
) -> DramState:
    """Install migrated pages into their slots; reset per-interval counters."""
    valid = plan.migrate
    n = d.slot_state.shape[0]
    # invalid lanes go out of bounds and are DROPPED (never index 0: a real
    # write to slot 0 must not race a stale no-op write)
    slot = jnp.where(valid, plan.dst_slot, n)
    state = d.slot_state.at[slot].set(jnp.int32(CLEAN), mode="drop")
    sp = d.slot_sp.at[slot].set(cand_sp, mode="drop")
    page = d.slot_page.at[slot].set(cand_page, mode="drop")
    reads = d.slot_reads.at[slot].set(0.0, mode="drop")
    writes = d.slot_writes.at[slot].set(0.0, mode="drop")
    touch = d.last_touch.at[slot].set(jnp.asarray(now, jnp.int32), mode="drop")
    return DramState(state, sp, page, reads, writes, touch)


def dram_release(d: DramState, slots: jax.Array) -> DramState:
    """Free a batch of slots (transactional aborts rolling back an install).

    slots: int32[K], -1 lanes ignored. Freed slots go back to FREE with clean
    counters, exactly the dram_init shape, so a later plan can reuse them as
    the cheapest victim class.
    """
    valid = slots >= 0
    n = d.slot_state.shape[0]
    # invalid lanes out of bounds -> dropped (same idiom as dram_apply_plan)
    s = jnp.where(valid, slots, n)
    return DramState(
        slot_state=d.slot_state.at[s].set(jnp.int32(FREE), mode="drop"),
        slot_sp=d.slot_sp.at[s].set(-1, mode="drop"),
        slot_page=d.slot_page.at[s].set(-1, mode="drop"),
        slot_reads=d.slot_reads.at[s].set(0.0, mode="drop"),
        slot_writes=d.slot_writes.at[s].set(0.0, mode="drop"),
        last_touch=d.last_touch.at[s].set(0, mode="drop"),
    )


def dram_new_interval(d: DramState) -> DramState:
    """Zero the per-interval access counters (keep residency + dirty bits)."""
    return DramState(
        slot_state=d.slot_state,
        slot_sp=d.slot_sp,
        slot_page=d.slot_page,
        slot_reads=jnp.zeros_like(d.slot_reads),
        slot_writes=jnp.zeros_like(d.slot_writes),
        last_touch=d.last_touch,
    )


def adapt_threshold(
    threshold: jax.Array,
    evictions: jax.Array,
    *,
    up_per_eviction: float = 8.0,
    decay: float = 0.9,
    floor: float = 0.0,
    ceil: float = 1e6,
) -> jax.Array:
    """§III-C: raise the benefit threshold with bidirectional traffic, decay it back.

    'we monitor the data traffic of bidirectional page migrations, and dynamically
    increase the threshold of migration benefit to select hotter small pages.'
    """
    t = threshold * decay + up_per_eviction * evictions.astype(jnp.float32)
    return jnp.clip(t, floor, ceil)
