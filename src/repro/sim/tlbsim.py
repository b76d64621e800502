"""Per-access translation+memory simulation (the sequential core of Layer A).

One lax.scan over the interval's accesses carries the TLB/bitmap-cache LRU state
and accumulates cycle/miss counters. Residency (which pages are DRAM-cached) is
fixed within an interval — migrations happen at interval boundaries (the paper's
history-based policy) — so residency arrives as a precomputed per-access vector.

Covers all five policies via static TranslationKind:
  flat4k  : single 4KB TLB, 4-ref PTW          (Flat-static, HSCC-4KB-mig)
  sp2m    : single 2MB TLB, 3-ref PTW          (HSCC-2MB-mig, DRAM-only)
  rainbow : split TLBs + bitmap cache + remap  (Fig. 6 four cases)
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.bitmap import BitmapCache, bitmap_cache_init, bitmap_cache_lookup
from repro.core.tlb import SplitTLB, split_tlb_init, split_tlb_lookup
from repro.sim.config import MachineConfig


class SimCounters(NamedTuple):
    cycles_tlb: jax.Array
    cycles_walk: jax.Array
    cycles_bitmap: jax.Array
    cycles_remap: jax.Array
    cycles_mem: jax.Array
    miss4_l1: jax.Array
    miss4_l2: jax.Array
    miss2m_l1: jax.Array
    miss2m_l2: jax.Array
    bmc_miss: jax.Array
    dram_reads: jax.Array
    dram_writes: jax.Array
    nvm_reads: jax.Array
    nvm_writes: jax.Array


def zero_counters() -> SimCounters:
    z = jnp.zeros((), jnp.float32)
    return SimCounters(*([z] * 14))


class SimState(NamedTuple):
    tlb4: SplitTLB
    tlb2m: SplitTLB
    bmc: BitmapCache
    t: jax.Array
    counters: SimCounters


def init_state(mc: MachineConfig) -> SimState:
    mk = lambda: split_tlb_init(
        mc.l1_tlb_entries, mc.l1_tlb_ways, mc.l2_tlb_entries, mc.l2_tlb_ways
    )
    return SimState(
        tlb4=mk(),
        tlb2m=mk(),
        bmc=bitmap_cache_init(mc.bitmap_cache_entries, mc.bitmap_cache_ways),
        t=jnp.zeros((), jnp.int32),
        counters=zero_counters(),
    )


def _acc(c: SimCounters, **kw) -> SimCounters:
    return c._replace(**{k: getattr(c, k) + v for k, v in kw.items()})


@functools.lru_cache(maxsize=None)
def make_access_step(kind: str, mc: MachineConfig):
    """Build the per-access scan step for one TranslationKind.

    Returned step: (SimState, (vpn, sp, in_dram, is_write)) -> (SimState, None).
    `run_interval` scans it over one interval's accesses; engine.simloop embeds
    the same step inside its whole-simulation scan so the device-resident
    engine is bit-identical to the host-looped path. Each structure's lookup
    runs under a named scope: "tlb4k", "tlb2m" and "bmc" (the bitmap cache).
    """

    l1l, l2l = mc.l1_tlb_lat, mc.l2_tlb_lat

    def step(st: SimState, xs):
        v, s, dram, wr = xs
        c = st.counters
        now = st.t
        mem_rd = jnp.where(dram, mc.t_dr, mc.t_nr)
        mem_wr = jnp.where(dram, mc.t_dw, mc.t_nw)
        mem_cost = jnp.where(wr, mem_wr, mem_rd)

        if kind == "flat4k":
            with jax.named_scope("tlb4k"):
                tlb4, h1, h2 = split_tlb_lookup(st.tlb4, v, now)
            walk = (~h1) & (~h2)
            c = _acc(
                c,
                cycles_tlb=l1l + jnp.where(~h1, l2l, 0.0),
                cycles_walk=jnp.where(walk, mc.ptw_refs_4k * mc.t_dr, 0.0),
                cycles_mem=mem_cost,
                miss4_l1=(~h1).astype(jnp.float32),
                miss4_l2=walk.astype(jnp.float32),
                dram_reads=(dram & ~wr).astype(jnp.float32),
                dram_writes=(dram & wr).astype(jnp.float32),
                nvm_reads=(~dram & ~wr).astype(jnp.float32),
                nvm_writes=(~dram & wr).astype(jnp.float32),
            )
            return SimState(tlb4, st.tlb2m, st.bmc, now + 1, c), None

        if kind == "sp2m":
            with jax.named_scope("tlb2m"):
                tlb2m, h1, h2 = split_tlb_lookup(st.tlb2m, s, now)
            walk = (~h1) & (~h2)
            c = _acc(
                c,
                cycles_tlb=l1l + jnp.where(~h1, l2l, 0.0),
                cycles_walk=jnp.where(walk, mc.ptw_refs_2m * mc.t_dr, 0.0),
                cycles_mem=mem_cost,
                miss2m_l1=(~h1).astype(jnp.float32),
                miss2m_l2=walk.astype(jnp.float32),
                dram_reads=(dram & ~wr).astype(jnp.float32),
                dram_writes=(dram & wr).astype(jnp.float32),
                nvm_reads=(~dram & ~wr).astype(jnp.float32),
                nvm_writes=(~dram & wr).astype(jnp.float32),
            )
            return SimState(st.tlb4, tlb2m, st.bmc, now + 1, c), None

        # ---- rainbow: Fig. 6 four cases ----
        # 4KB TLB holds only DRAM-cached pages; consulted in parallel with the
        # superpage TLB. Fill 4KB TLB only when the access resolves to DRAM.
        with jax.named_scope("tlb4k"):
            tlb4, h41, h42 = split_tlb_lookup(st.tlb4, v, now, fill=dram)
        hit4 = (h41 | h42) & dram  # stale-proof: entry implies residency
        with jax.named_scope("tlb2m"):
            tlb2m, h21, h22 = split_tlb_lookup(st.tlb2m, s, now)
        sp_hit = h21 | h22
        sptw = ~sp_hit

        # Cases 3/4: 4KB miss -> consult bitmap (cache) for the home superpage.
        need_bitmap = ~hit4
        with jax.named_scope("bmc"):
            bmc, bmc_hit = bitmap_cache_lookup(st.bmc, s, now)
        bmc_miss = need_bitmap & ~bmc_hit
        cost_bitmap = jnp.where(
            need_bitmap, mc.bitmap_cache_lat + jnp.where(bmc_miss, mc.t_nr, 0.0), 0.0
        )
        # migrated & 4KB-missed -> remap pointer read from NVM (one t_nr)
        remap_read = need_bitmap & dram
        cost_remap = jnp.where(remap_read, mc.remap_read_lat, 0.0)

        cost_tlb = l1l + jnp.where(~h41 & ~h21, l2l, 0.0)
        cost_walk = jnp.where(need_bitmap & sptw, mc.ptw_refs_2m * mc.t_dr, 0.0)

        c = _acc(
            c,
            cycles_tlb=cost_tlb,
            cycles_walk=cost_walk,
            cycles_bitmap=cost_bitmap,
            cycles_remap=cost_remap,
            cycles_mem=mem_cost,
            miss4_l1=(dram & ~h41).astype(jnp.float32),
            miss4_l2=(dram & ~hit4).astype(jnp.float32),
            miss2m_l1=(~h21).astype(jnp.float32),
            miss2m_l2=sptw.astype(jnp.float32),
            bmc_miss=bmc_miss.astype(jnp.float32),
            dram_reads=(dram & ~wr).astype(jnp.float32),
            dram_writes=(dram & wr).astype(jnp.float32),
            nvm_reads=(~dram & ~wr).astype(jnp.float32),
            nvm_writes=(~dram & wr).astype(jnp.float32),
        )
        return SimState(tlb4, tlb2m, bmc, now + 1, c), None

    return step


@functools.partial(jax.jit, static_argnames=("kind", "mc"))
def run_interval(
    kind: str,
    mc: MachineConfig,
    state: SimState,
    vpn: jax.Array,  # int32[A] 4KB page id (global)
    sp: jax.Array,  # int32[A] superpage id
    in_dram: jax.Array,  # bool[A] residency at interval start
    is_write: jax.Array,  # bool[A]
) -> SimState:
    """Scan the interval's accesses; returns state with accumulated counters."""
    state, _ = jax.lax.scan(
        make_access_step(kind, mc), state, (vpn, sp, in_dram, is_write)
    )
    return state


# ---------------------------------------------------------------------------
# Fast per-interval hot path (bit-identical to scanning make_access_step)
# ---------------------------------------------------------------------------
#
# The reference scan above carries the full SimState (TLB tables + all 14
# float32 counters) and re-derives every per-access quantity inside the scan
# body. Most of that work is provably order-independent:
#
#   * tier classification + memory cost per access depend only on the chunk
#     (in_dram, is_write), never on TLB state -> hoisted out of the scan and
#     computed vectorized. Elementwise ops in the same dtype are bitwise
#     equal wherever they run.
#   * COUNT-like counters (miss counts, tier read/write counts, bmc misses)
#     accumulate +0.0/+1.0 in float32. Every partial sum is an integer, and
#     integers are exact in float32 below 2**24 — so summing the batch as
#     int32 and adding the total once yields the SAME final float32 value as
#     the reference's one-add-per-access, for any access order. (Invariant:
#     cumulative per-counter totals stay < 2**24 ≈ 16.7M accesses; current
#     workloads peak around 1M. Documented in docs/engine.md.)
#
# What stays serial — and why:
#
#   * CYCLE counters (cycles_tlb/walk/bitmap/remap/mem) accumulate
#     NON-integer float32 values (e.g. t_dr = 43.2), and float addition is
#     not associative: any reordering changes low bits, which the HSCC
#     parity snapshot (rel-err 0.0 on IPC) would catch. They remain
#     sequential adds, in reference order, inside the scan.
#   * The set-associative LRU TLB/bitmap-cache state is genuinely
#     order-dependent (each lookup's hit and victim depend on every prior
#     access in the same set), so the tag/lru updates remain a scan.
#
# The scan body itself is slimmed two ways: the split-TLB L1 probe +
# conditional L1 back-fill pair collapses into ONE combined update
# (_fused_split_lookup below — provably the same final state), and the scan
# is unrolled (structural only: same ops, same order, same results).

INTERVAL_UNROLL = 4


def _probe(tags: jax.Array, lru: jax.Array, sets: int, v: jax.Array):
    """Read one set's line once. Returns (s, line, lru_line, hit_way, hit)."""
    if sets == 1:
        s = jnp.int32(0)
        line, lru_line = tags[0], lru[0]
    else:
        s = (v % sets).astype(jnp.int32)
        line = jax.lax.dynamic_index_in_dim(tags, s, keepdims=False)
        lru_line = jax.lax.dynamic_index_in_dim(lru, s, keepdims=False)
    hit_way = line == v
    return s, line, lru_line, hit_way, hit_way.any()


def _way_of(hit, hit_way, lru_line) -> jax.Array:
    return jnp.where(hit, jnp.argmax(hit_way), jnp.argmin(lru_line)).astype(
        jnp.int32
    )


def _write_entry(tags, lru, s, way, tag_v, lru_v):
    """Single-entry (s, way) update via dynamic_update_slice (no scatter)."""
    tags = jax.lax.dynamic_update_slice(tags, tag_v.reshape(1, 1), (s, way))
    lru = jax.lax.dynamic_update_slice(lru, lru_v.reshape(1, 1), (s, way))
    return tags, lru


def _pick(line: jax.Array, way: jax.Array) -> jax.Array:
    return jax.lax.dynamic_index_in_dim(line, way, keepdims=False)


def _fused_split_lookup(
    st: SplitTLB, vpn: jax.Array, now: jax.Array, fill: bool | jax.Array = True
) -> tuple[SplitTLB, jax.Array, jax.Array]:
    """split_tlb_lookup with the two L1 touches fused into one write.

    The reference does three tlb_lookup calls: an L1 probe (fill=False, which
    writes lru=now only on hit), the L2 lookup, then a conditional L1
    back-fill. Because the probe writes nothing on a miss, the back-fill's
    victim (argmin lru) is computed on unchanged state — so both L1 touches
    write the same (tag=vpn, lru=now) at the same way under the combined
    condition h1 | h2 | fill. One probe + one conditional single-entry write
    replaces two full lookups; final state and (h1, h2) are bit-identical.
    Set lines are gathered once and reused for the keep-old branch of the
    conditional write (the reference re-gathers `tags[s, way]`; same values).
    """
    from repro.core.tlb import TLBState

    v = vpn.astype(jnp.int32)
    now32 = now.astype(jnp.int32)
    fill = jnp.asarray(fill)
    l1, l2 = st.l1, st.l2

    s1, line1, lrul1, hw1, h1 = _probe(l1.tags, l1.lru, l1.sets, v)
    s2, line2, lrul2, hw2, h2 = _probe(l2.tags, l2.lru, l2.sets, v)

    way2 = _way_of(h2, hw2, lrul2)
    do2 = h2 | fill
    t2, r2 = _write_entry(
        l2.tags, l2.lru, s2, way2,
        jnp.where(do2, v, _pick(line2, way2)),
        jnp.where(do2, now32, _pick(lrul2, way2)),
    )

    way1 = _way_of(h1, hw1, lrul1)
    do1 = h1 | h2 | fill
    t1, r1 = _write_entry(
        l1.tags, l1.lru, s1, way1,
        jnp.where(do1, v, _pick(line1, way1)),
        jnp.where(do1, now32, _pick(lrul1, way1)),
    )

    return (
        SplitTLB(
            l1=TLBState(tags=t1, lru=r1, sets=l1.sets, ways=l1.ways),
            l2=TLBState(tags=t2, lru=r2, sets=l2.sets, ways=l2.ways),
        ),
        h1,
        h2,
    )


def _fast_bmc_lookup(bmc, psn: jax.Array, now: jax.Array):
    """bitmap_cache_lookup with one probe + dynamic_update_slice writes."""
    from repro.core.bitmap import BitmapCache

    p = psn.astype(jnp.int32)
    s, _, lrul, hw, hit = _probe(bmc.tags, bmc.lru, bmc.tags.shape[0], p)
    way = _way_of(hit, hw, lrul)
    tags, lru = _write_entry(
        bmc.tags, bmc.lru, s, way, p, now.astype(jnp.int32)
    )
    return BitmapCache(tags=tags, lru=lru), hit


def _count(x: jax.Array) -> jax.Array:
    """Batch count of a bool vector, as the float32 the reference accumulates."""
    return x.sum(dtype=jnp.int32).astype(jnp.float32)


@functools.lru_cache(maxsize=None)
def make_interval_runner(kind: str, mc: MachineConfig, unroll: int = INTERVAL_UNROLL):
    """Build the fast-path interval executor for one TranslationKind.

    Same signature as scanning `make_access_step` over the interval:
    (SimState, vpn, sp, in_dram, is_write) -> SimState, and bit-identical to
    it (tests/test_hotpath.py pins the equivalence property-wise; the
    engine-vs-eager suite pins it end-to-end). Memoized per (kind, mc) so jit
    tracing caches see one function identity. The lookups carry the named
    scopes of make_access_step.
    """

    l1l, l2l = mc.l1_tlb_lat, mc.l2_tlb_lat
    walk4 = mc.ptw_refs_4k * mc.t_dr
    walk2m = mc.ptw_refs_2m * mc.t_dr

    def run(st: SimState, vpn, sp, in_dram, is_write) -> SimState:
        c = st.counters
        # --- hoisted: order-independent per-access quantities (vectorized) ---
        mem_rd = jnp.where(in_dram, mc.t_dr, mc.t_nr)
        mem_wr = jnp.where(in_dram, mc.t_dw, mc.t_nw)
        mem_cost = jnp.where(is_write, mem_wr, mem_rd)
        dram_reads = c.dram_reads + _count(in_dram & ~is_write)
        dram_writes = c.dram_writes + _count(in_dram & is_write)
        nvm_reads = c.nvm_reads + _count(~in_dram & ~is_write)
        nvm_writes = c.nvm_writes + _count(~in_dram & is_write)

        zi = jnp.zeros((), jnp.int32)

        if kind in ("flat4k", "sp2m"):
            tlb0 = st.tlb4 if kind == "flat4k" else st.tlb2m
            key = vpn if kind == "flat4k" else sp
            walk_cost = walk4 if kind == "flat4k" else walk2m
            scope = "tlb4k" if kind == "flat4k" else "tlb2m"

            def body(carry, xs):
                tlb, t, ctlb, cwalk, cmem, m1, m2 = carry
                v, mcost = xs
                with jax.named_scope(scope):
                    tlb, h1, h2 = _fused_split_lookup(tlb, v, t)
                walk = (~h1) & (~h2)
                ctlb = ctlb + (l1l + jnp.where(~h1, l2l, 0.0))
                cwalk = cwalk + jnp.where(walk, walk_cost, 0.0)
                cmem = cmem + mcost
                m1 = m1 + (~h1).astype(jnp.int32)
                m2 = m2 + walk.astype(jnp.int32)
                return (tlb, t + 1, ctlb, cwalk, cmem, m1, m2), None

            (tlb, t, ctlb, cwalk, cmem, m1, m2), _ = jax.lax.scan(
                body,
                (tlb0, st.t, c.cycles_tlb, c.cycles_walk, c.cycles_mem, zi, zi),
                (key, mem_cost),
                unroll=unroll,
            )
            if kind == "flat4k":
                counters = c._replace(
                    cycles_tlb=ctlb, cycles_walk=cwalk, cycles_mem=cmem,
                    miss4_l1=c.miss4_l1 + m1.astype(jnp.float32),
                    miss4_l2=c.miss4_l2 + m2.astype(jnp.float32),
                    dram_reads=dram_reads, dram_writes=dram_writes,
                    nvm_reads=nvm_reads, nvm_writes=nvm_writes,
                )
                return SimState(tlb, st.tlb2m, st.bmc, t, counters)
            counters = c._replace(
                cycles_tlb=ctlb, cycles_walk=cwalk, cycles_mem=cmem,
                miss2m_l1=c.miss2m_l1 + m1.astype(jnp.float32),
                miss2m_l2=c.miss2m_l2 + m2.astype(jnp.float32),
                dram_reads=dram_reads, dram_writes=dram_writes,
                nvm_reads=nvm_reads, nvm_writes=nvm_writes,
            )
            return SimState(st.tlb4, tlb, st.bmc, t, counters)

        # ---- rainbow: Fig. 6 four cases, slim carry ----
        def body(carry, xs):
            tlb4, tlb2m, bmc, t, ctlb, cwalk, cbmp, crmp, cmem, m41, m42, m21, m22, mb = carry
            v, s, dram, mcost = xs
            with jax.named_scope("tlb4k"):
                tlb4, h41, h42 = _fused_split_lookup(tlb4, v, t, fill=dram)
            hit4 = (h41 | h42) & dram
            with jax.named_scope("tlb2m"):
                tlb2m, h21, h22 = _fused_split_lookup(tlb2m, s, t)
            sptw = ~(h21 | h22)
            need_bitmap = ~hit4
            with jax.named_scope("bmc"):
                bmc, bmc_hit = _fast_bmc_lookup(bmc, s, t)
            bmc_miss = need_bitmap & ~bmc_hit
            ctlb = ctlb + (l1l + jnp.where(~h41 & ~h21, l2l, 0.0))
            cwalk = cwalk + jnp.where(need_bitmap & sptw, walk2m, 0.0)
            cbmp = cbmp + jnp.where(
                need_bitmap,
                mc.bitmap_cache_lat + jnp.where(bmc_miss, mc.t_nr, 0.0),
                0.0,
            )
            crmp = crmp + jnp.where(need_bitmap & dram, mc.remap_read_lat, 0.0)
            cmem = cmem + mcost
            m41 = m41 + (dram & ~h41).astype(jnp.int32)
            m42 = m42 + (dram & ~hit4).astype(jnp.int32)
            m21 = m21 + (~h21).astype(jnp.int32)
            m22 = m22 + sptw.astype(jnp.int32)
            mb = mb + bmc_miss.astype(jnp.int32)
            return (
                tlb4, tlb2m, bmc, t + 1,
                ctlb, cwalk, cbmp, crmp, cmem, m41, m42, m21, m22, mb,
            ), None

        carry0 = (
            st.tlb4, st.tlb2m, st.bmc, st.t,
            c.cycles_tlb, c.cycles_walk, c.cycles_bitmap, c.cycles_remap,
            c.cycles_mem, zi, zi, zi, zi, zi,
        )
        (
            tlb4, tlb2m, bmc, t,
            ctlb, cwalk, cbmp, crmp, cmem, m41, m42, m21, m22, mb,
        ), _ = jax.lax.scan(
            body, carry0, (vpn, sp, in_dram, mem_cost), unroll=unroll
        )
        counters = c._replace(
            cycles_tlb=ctlb, cycles_walk=cwalk, cycles_bitmap=cbmp,
            cycles_remap=crmp, cycles_mem=cmem,
            miss4_l1=c.miss4_l1 + m41.astype(jnp.float32),
            miss4_l2=c.miss4_l2 + m42.astype(jnp.float32),
            miss2m_l1=c.miss2m_l1 + m21.astype(jnp.float32),
            miss2m_l2=c.miss2m_l2 + m22.astype(jnp.float32),
            bmc_miss=c.bmc_miss + mb.astype(jnp.float32),
            dram_reads=dram_reads, dram_writes=dram_writes,
            nvm_reads=nvm_reads, nvm_writes=nvm_writes,
        )
        return SimState(tlb4, tlb2m, bmc, t, counters)

    return run


@functools.partial(jax.jit, static_argnames=("kind", "mc"))
def run_interval_fast(
    kind: str,
    mc: MachineConfig,
    state: SimState,
    vpn: jax.Array,
    sp: jax.Array,
    in_dram: jax.Array,
    is_write: jax.Array,
) -> SimState:
    """Jitted fast-path counterpart of run_interval (bit-identical)."""
    return make_interval_runner(kind, mc)(state, vpn, sp, in_dram, is_write)
