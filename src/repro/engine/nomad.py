"""Nomad-style transactional asynchronous migration (PAPERS.md: Nomad '24).

Rainbow's step program stops the world at interval end: the whole migration
plan's traffic lands on the queues as one bulk charge at `t_end`. Nomad
migrates *transactionally* — the copy proceeds concurrently with demand
access, writes to a page mid-copy abort the transaction, and a migrating
page is temporarily resident in both tiers. This module models that family
as a wrapper AROUND the unchanged rainbow controller: admission, selection,
remap install/evict, and threshold adaptation are `core.rainbow` verbatim;
what changes is (a) WHEN the planned traffic is charged and (b) what happens
to in-flight pages that get written.

State added on top of RainbowState (all scan-carried, fixed shapes):

  * an in-flight ring of the last `W-1` generations' migrated lanes
    (`tx_sp/tx_page/tx_slot`, each int32[W-1, K]; row 0 = newest), where
    `W = policy.async_window` and `K = policy.max_promotions`;
  * per-tier installment schedules `pend_dram/pend_nvm` (f32[W]): slot j
    holds the bulk cycles due at the j-th upcoming interval end. A
    generation planned at the end of interval t spreads its
    `timing.traffic.migration_cycles` total evenly over the ends of
    intervals t .. t+W-1 (the first installment lands exactly where rainbow
    lands its full charge);
  * `aborts_total` (int32), surfaced as SimMetrics.mig_aborts.

Interval close (`nomad_close`) runs, in order:

  1. abort detection: a ring lane whose page was WRITTEN this interval (and
     that still owns its DRAM slot) aborts — remap entry evicted, slot
     released, remaining installments (including this interval's) canceled
     at `(mig_page_cost / 2) / W` per tier per lane, lane cleared, the page
     shot down in the 4KB TLB like an eviction. A lane whose slot was
     reassigned by a later plan is implicitly terminated, NOT an abort
     (rolling it back would clobber the new occupant);
  2. the unchanged rainbow plan/apply on the rolled-back state;
  3. installment bookkeeping: add the new generation's per-tier total / W
     into all W pend slots, emit `pend[0]` as this interval's bulk charge,
     shift the schedule, and rotate the new generation into ring row 0
     (row W-2 — the generation whose last installment was just charged —
     completes and drops out).

Degenerate invariant (the differential gate, tests/test_nomad.py): with
`async_window == 1` the ring is empty (shape (0, K)) and every async code
path is STATICALLY skipped — the bulk charge is exactly
`migration_cycles(...)` (0.0 + C/1.0 is bitwise C in f32) — so the nomad
step program is bit-identical to the synchronous rainbow program.

Simplifications (documented, deliberate):
  * evictions triggered by an aborted generation's original plan are not
    rolled back (their writeback traffic already happened);
  * a mid-flight page evicted by a later plan keeps its installments (the
    copy bandwidth was already being consumed);
  * the flat cost model prices each generation in full at plan time even if
    it later aborts — pessimistic; the queueing model cancels installments.

Imports only core/timing/utils (never repro.sim): engine -> timing must not
cycle back through sim.__init__, same constraint as timing/traffic.py.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import migration, rainbow as rb
from repro.core.rainbow import IntervalReport, RainbowConfig, RainbowState
from repro.core.remap import remap_evict, translate
from repro.timing import traffic
from repro.utils import pytree_dataclass


@pytree_dataclass
class NomadState:
    """RainbowState + the transactional in-flight ring + installment plan."""

    rb: RainbowState
    tx_sp: jax.Array  # int32[W-1, K]; row 0 = newest in-flight generation
    tx_page: jax.Array  # int32[W-1, K]
    tx_slot: jax.Array  # int32[W-1, K]
    pend_dram: jax.Array  # f32[W]; slot j due at the j-th upcoming interval end
    pend_nvm: jax.Array  # f32[W]
    aborts_total: jax.Array  # int32 cumulative aborted transactions


class NomadReport(NamedTuple):
    """rainbow's IntervalReport + the async layer's outputs."""

    rb: IntervalReport
    bulk_dram: jax.Array  # f32: this interval's DRAM-tier installment
    bulk_nvm: jax.Array  # f32: this interval's NVM-tier installment
    n_aborts: jax.Array  # int32: transactions aborted this interval
    abort_vpn: jax.Array | None  # int32[(W-1)*K] vpns to shoot down, or None


def _window(cfg: RainbowConfig) -> int:
    return cfg.policy.async_window


def nomad_init(cfg: RainbowConfig) -> NomadState:
    w, k = _window(cfg), cfg.policy.max_promotions
    ring = jnp.full((w - 1, k), -1, jnp.int32)
    return NomadState(
        rb=rb.rainbow_init(cfg),
        tx_sp=ring,
        tx_page=ring,
        tx_slot=ring,
        pend_dram=jnp.zeros((w,), jnp.float32),
        pend_nvm=jnp.zeros((w,), jnp.float32),
        aborts_total=jnp.zeros((), jnp.int32),
    )


def nomad_observe(
    cfg: RainbowConfig,
    st: NomadState,
    sp: jax.Array,
    page: jax.Array,
    is_write: jax.Array,
    now: jax.Array,
) -> NomadState:
    """Counting is the unchanged rainbow observe: accesses to in-flight pages
    count on their DRAM slot (the remap is installed at plan time), so an
    aborted page loses that interval's heat with its slot and must re-earn
    admission — the retry is by re-election, not a queued redo."""
    return dataclasses.replace(
        st, rb=rb.observe(cfg, st.rb, sp, page, is_write, now)
    )


def _in_flight_map(cfg: RainbowConfig, st: NomadState) -> jax.Array:
    """bool[num_sp * pages_per_sp]: vpn currently mid-copy (any ring row)."""
    nvpn = cfg.num_superpages * cfg.pages_per_sp
    lane_vpn = st.tx_sp * cfg.pages_per_sp + st.tx_page
    idx = jnp.where(st.tx_sp >= 0, lane_vpn, nvpn).reshape(-1)
    return jnp.zeros((nvpn,), bool).at[idx].set(True, mode="drop")


def residency(
    cfg: RainbowConfig,
    st: NomadState,
    sp: jax.Array,
    page: jax.Array,
    is_write: jax.Array,
) -> jax.Array:
    """Per-access fast-tier residency under the transactional copy window.

    Exclusive residency (shadow_residency=False) is rainbow's: the remap
    flips at plan time, every access to an installed page prices as DRAM.
    Shadow residency serves READS from the cheaper tier (DRAM: t_dr < t_nr
    on every preset) but WRITES to an in-flight page from the source NVM
    copy — the destination copy is not yet consistent, which is exactly why
    abort_on_write kills the transaction.
    """
    base, _ = translate(st.rb.remap, sp, page)
    if _window(cfg) == 1 or not cfg.policy.shadow_residency:
        return base
    in_flight = _in_flight_map(cfg, st)[sp * cfg.pages_per_sp + page]
    return base & ~(is_write & in_flight)


def _detect_aborts(cfg: RainbowConfig, st: NomadState, sp, page, is_write,
                   mc):
    """(new_st, n_aborts, abort_vpn): roll back written in-flight lanes."""
    nvpn = cfg.num_superpages * cfg.pages_per_sp
    wr_vpn = jnp.where(is_write, sp * cfg.pages_per_sp + page, nvpn)
    written = jnp.zeros((nvpn,), bool).at[wr_vpn].set(True, mode="drop")

    lane_valid = st.tx_sp >= 0
    lane_vpn = jnp.where(
        lane_valid, st.tx_sp * cfg.pages_per_sp + st.tx_page, 0
    )
    dram = st.rb.dram
    slot = jnp.where(lane_valid, st.tx_slot, 0)
    # a later plan may have reassigned the slot: that lane is terminated,
    # not aborted (rolling back would clobber the new occupant)
    owns = (
        lane_valid
        & (st.tx_slot >= 0)
        & (dram.slot_sp[slot] == st.tx_sp)
        & (dram.slot_page[slot] == st.tx_page)
    )
    aborted = owns & written[lane_vpn]  # bool[W-1, K]

    ab_sp = jnp.where(aborted, st.tx_sp, -1)
    ab_page = jnp.where(aborted, st.tx_page, -1)
    ab_slot = jnp.where(aborted, st.tx_slot, -1)
    remap = remap_evict(st.rb.remap, ab_sp.reshape(-1), ab_page.reshape(-1))
    dram = migration.dram_release(dram, ab_slot.reshape(-1))

    # cancel the remaining installments: a lane in ring row r has
    # W-1-r installments outstanding (pend slots 0 .. W-2-r), each worth
    # (mig_page_cost / 2) / W cycles per tier
    w = _window(cfg)
    share = jnp.float32(mc.mig_page_cost / 2.0 / w)
    n_ab_row = aborted.sum(axis=1).astype(jnp.float32)  # f32[W-1]
    cums = jnp.cumsum(n_ab_row)
    cancel = jnp.concatenate([cums[::-1], jnp.zeros((1,), jnp.float32)])
    pend_dram = jnp.maximum(st.pend_dram - share * cancel, 0.0)
    pend_nvm = jnp.maximum(st.pend_nvm - share * cancel, 0.0)

    n_aborts = aborted.sum().astype(jnp.int32)
    new_st = dataclasses.replace(
        st,
        rb=dataclasses.replace(st.rb, remap=remap, dram=dram),
        tx_sp=jnp.where(aborted, -1, st.tx_sp),
        tx_page=jnp.where(aborted, -1, st.tx_page),
        tx_slot=jnp.where(aborted, -1, st.tx_slot),
        pend_dram=pend_dram,
        pend_nvm=pend_nvm,
        aborts_total=st.aborts_total + n_aborts,
    )
    abort_vpn = jnp.where(
        aborted, st.tx_sp * cfg.pages_per_sp + st.tx_page, -1
    ).reshape(-1)
    return new_st, n_aborts, abort_vpn


def nomad_close(
    cfg: RainbowConfig,
    st: NomadState,
    sp: jax.Array,
    page: jax.Array,
    is_write: jax.Array,
    timing,
    mc,
) -> tuple[NomadState, NomadReport]:
    """End-of-interval: aborts -> rainbow plan/apply -> installment roll."""
    w = _window(cfg)

    n_aborts = jnp.zeros((), jnp.int32)
    abort_vpn = None
    if w > 1 and cfg.policy.abort_on_write:
        st, n_aborts, abort_vpn = _detect_aborts(
            cfg, st, sp, page, is_write, mc
        )

    rb_st, rep = rb.end_interval(cfg, st.rb, timing)

    # generation traffic, priced exactly like a rainbow interval, spread
    # evenly over the next w interval ends (slot 0 = THIS interval's end)
    c_dram, c_nvm = traffic.migration_cycles(
        "nomad", mc, rep.n_migrated, rep.n_evicted, rep.n_dirty_evicted
    )
    pend_dram = st.pend_dram + c_dram / jnp.float32(w)
    pend_nvm = st.pend_nvm + c_nvm / jnp.float32(w)
    bulk_dram, bulk_nvm = pend_dram[0], pend_nvm[0]
    zero = jnp.zeros((1,), jnp.float32)
    pend_dram = jnp.concatenate([pend_dram[1:], zero])
    pend_nvm = jnp.concatenate([pend_nvm[1:], zero])

    if w > 1:
        # rotate the new generation into row 0; row w-2 (its last
        # installment just charged) completes and leaves the ring
        new_sp = jnp.where(rep.plan.migrate, rep.cand_sp, -1)
        new_page = jnp.where(rep.plan.migrate, rep.cand_page, -1)
        new_slot = jnp.where(rep.plan.migrate, rep.plan.dst_slot, -1)
        tx_sp = jnp.concatenate([new_sp[None], st.tx_sp[: w - 2]])
        tx_page = jnp.concatenate([new_page[None], st.tx_page[: w - 2]])
        tx_slot = jnp.concatenate([new_slot[None], st.tx_slot[: w - 2]])
    else:
        tx_sp, tx_page, tx_slot = st.tx_sp, st.tx_page, st.tx_slot

    new_st = NomadState(
        rb=rb_st,
        tx_sp=tx_sp,
        tx_page=tx_page,
        tx_slot=tx_slot,
        pend_dram=pend_dram,
        pend_nvm=pend_nvm,
        aborts_total=st.aborts_total,
    )
    report = NomadReport(
        rb=rep,
        bulk_dram=bulk_dram,
        bulk_nvm=bulk_nvm,
        n_aborts=n_aborts,
        abort_vpn=abort_vpn,
    )
    return new_st, report


def nomad_interval(
    cfg: RainbowConfig,
    st: NomadState,
    sp: jax.Array,
    page: jax.Array,
    is_write: jax.Array,
    timing,
    mc,
) -> tuple[NomadState, NomadReport]:
    """One full interval (observe batch + close), scannable — the nomad
    counterpart of core.rainbow.interval_step. The close runs under the
    "plan" scope, as engine.profile times it."""
    with jax.named_scope("observe"):
        st = nomad_observe(cfg, st, sp, page, is_write, st.rb.interval)
    with jax.named_scope("plan"):
        return nomad_close(cfg, st, sp, page, is_write, timing, mc)
