"""Plain reference of one hybrid-memory simulation (Rainbow, arXiv:1806.00776).

Written from the paper's mechanisms and the configuration file alone; it
imports nothing of the system under test. One call runs a whole simulation
of a ZipfHotspot workload under `flat-static` (4 KB TLB, static hash
placement, no controller) or `rainbow` (split 4 KB / 2 MB TLBs, bitmap cache,
remap reads, two-stage counting, utility admission into DRAM slots, TLB
shootdowns on eviction), and returns the simulation's metrics as a flat dict
of floats.

The straightforward form: the per-access translation walk is one `lax.scan`
step per access with every TLB lookup spelled out, the interval controller
is plain array code run once per interval, and host totals are Python
floats. Cycle counters are `acc_dtype` (float32, as the configuration
states); the control passes bfloat16.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

PAGES_PER_SP = 512
COUNTER_MAX = (1 << 15) - 1
FREE, CLEAN, DIRTY = 0, 1, 2
# Static placement of flat-static: a page lives in DRAM when its
# multiplicative hash lands in the DRAM share of MOD buckets.
FLAT_HASH_KNUTH = 2654435761
FLAT_HASH_MOD = 997


# ---------------------------------------------------------------------------
# Workload: a seed-fixed hot set with zipf-ranked traffic (paper Tables I/II)
# ---------------------------------------------------------------------------

_SALT_SETUP, _SALT_HOT, _SALT_COLD, _SALT_SHUFFLE, _SALT_WRITE = 101, 7, 11, 13, 17
_SALT_BUCKET, _SALT_COUNT, _SALT_RANK, _SALT_TIE = 23, 29, 31, 37
_TABLE2_LOWERS = (1, 33, 65, 129, 257, 385)
_TABLE2_UPPERS = (32, 64, 128, 256, 384, 512)


def workload_shape(cfg: dict) -> dict:
    """Pages, hot pages and Table II buckets of the workload, as run."""
    fp = int(cfg["footprint_pages"])
    ws = min(int(cfg["working_set_pages"]), fp)
    n_hot = max(1, int(ws * cfg["hot_page_pct"] / 100.0))
    scale = int(cfg["scale_down"])
    buckets = tuple(
        (float(w), max(1, lo // scale), max(1, hi // scale))
        for w, lo, hi in zip(cfg["sp_hot_dist"], _TABLE2_LOWERS, _TABLE2_UPPERS)
        if w > 0
    )
    return {"footprint_pages": fp, "n_hot": n_hot, "buckets": buckets,
            "num_superpages": -(-fp // PAGES_PER_SP)}


def _hot_set(cfg: dict, seed) -> jax.Array:
    """The hot pages: each superpage draws a Table II bucket, then a quota."""
    shape = workload_shape(cfg)
    fp, n_sp = shape["footprint_pages"], shape["num_superpages"]
    key = jax.random.fold_in(jax.random.PRNGKey(seed), _SALT_SETUP)
    w = np.asarray([b[0] for b in shape["buckets"]], np.float64)
    cdf = np.cumsum(w / w.sum()).astype(np.float32)
    cdf[-1] = np.float32(1.0)
    lo = jnp.asarray([b[1] for b in shape["buckets"]], jnp.int32)
    hi = jnp.asarray([b[2] for b in shape["buckets"]], jnp.int32)
    u_b = jax.random.uniform(jax.random.fold_in(key, _SALT_BUCKET), (n_sp,), jnp.float32)
    b = jnp.clip(jnp.searchsorted(jnp.asarray(cdf), u_b, side="right"), 0, len(cdf) - 1)
    u_c = jax.random.uniform(jax.random.fold_in(key, _SALT_COUNT), (n_sp,), jnp.float32)
    quota = jnp.minimum(lo[b] + (u_c * (hi[b] - lo[b] + 1).astype(jnp.float32)).astype(jnp.int32), hi[b])
    grid = jnp.arange(n_sp * PAGES_PER_SP, dtype=jnp.int32).reshape(n_sp, PAGES_PER_SP)
    valid = grid < fp
    quota = jnp.minimum(quota, valid.sum(axis=1).astype(jnp.int32))
    r_u = jax.random.uniform(jax.random.fold_in(key, _SALT_RANK), (n_sp, PAGES_PER_SP), jnp.float32)
    rank = jnp.argsort(jnp.argsort(jnp.where(valid, r_u, 2.0), axis=1), axis=1)
    eligible = (rank < quota[:, None]) & valid
    tie = jax.random.uniform(jax.random.fold_in(key, _SALT_TIE), (n_sp, PAGES_PER_SP), jnp.float32)
    sort_key = jnp.where(valid, jnp.where(eligible, tie, 2.0 + tie), 4.0 + tie)
    return grid.reshape(-1)[jnp.argsort(sort_key.reshape(-1))][: shape["n_hot"]]


def interval_trace(cfg: dict, hot: jax.Array, seed, interval, accesses: int):
    """One interval's (page, is_write): hot traffic zipf over the hot set,
    the rest uniform over the footprint, keyed by fold_in(seed, interval)."""
    fp = int(cfg["footprint_pages"])
    n_hot = hot.shape[0]
    ranks = np.arange(1, n_hot + 1, dtype=np.float64) ** (-cfg["zipf_alpha"])
    cdf = np.cumsum(ranks / ranks.sum()).astype(np.float32)
    cdf[-1] = np.float32(1.0)
    key = jax.random.fold_in(jax.random.PRNGKey(seed), interval)
    u = jax.random.uniform(jax.random.fold_in(key, _SALT_HOT), (accesses,), jnp.float32)
    hot_pick = hot[jnp.clip(jnp.searchsorted(jnp.asarray(cdf), u, side="right"), 0, n_hot - 1)]
    cold = jax.random.randint(jax.random.fold_in(key, _SALT_COLD), (accesses,), 0, fp, jnp.int32)
    u_mix = jax.random.uniform(jax.random.fold_in(key, _SALT_SHUFFLE), (accesses,), jnp.float32)
    pages = jnp.where(u_mix < cfg["hot_traffic"], hot_pick, cold)
    wr = jax.random.uniform(jax.random.fold_in(key, _SALT_WRITE), (accesses,), jnp.float32) < cfg["write_ratio"]
    return pages.astype(jnp.int32), wr


# ---------------------------------------------------------------------------
# Machine: cycle constants from the configuration (Table IV)
# ---------------------------------------------------------------------------


def machine(cfg: dict) -> dict:
    ghz = cfg["cpu_ghz"]
    mig = cfg["page_bytes"] / cfg["mig_bandwidth_bytes_per_s"] * 1e9 * ghz * 2
    return {
        "t_dr": cfg["t_dram_read_ns"] * ghz, "t_dw": cfg["t_dram_write_ns"] * ghz,
        "t_nr": cfg["t_nvm_read_ns"] * ghz, "t_nw": cfg["t_nvm_write_ns"] * ghz,
        "remap": cfg["t_nvm_read_ns"] * ghz, "mig_page": mig, "writeback_page": mig,
        "walk4": cfg["ptw_refs_4k"] * (cfg["t_dram_read_ns"] * ghz),
        "walk2m": cfg["ptw_refs_2m"] * (cfg["t_dram_read_ns"] * ghz),
        "dram_slots": cfg["dram_bytes"] // cfg["page_bytes"],
    }


# ---------------------------------------------------------------------------
# Set-associative LRU caches (TLB levels and the bitmap cache)
# ---------------------------------------------------------------------------


def _cache(entries: int, ways: int):
    sets = max(1, entries // ways)
    return (jnp.full((sets, ways), -1, jnp.int32), jnp.zeros((sets, ways), jnp.int32))


def _lookup(c, key, now, fill):
    """Probe one set; on a hit refresh the way's LRU time, on a miss fill the
    least recently used way when `fill` holds. Returns (cache', hit)."""
    tags, lru = c
    s = (key % tags.shape[0]).astype(jnp.int32)
    line = jax.lax.dynamic_index_in_dim(tags, s, keepdims=False)
    lru_line = jax.lax.dynamic_index_in_dim(lru, s, keepdims=False)
    hit = (line == key).any()
    way = jnp.where(hit, jnp.argmax(line == key), jnp.argmin(lru_line)).astype(jnp.int32)
    write = hit | fill
    tag = jnp.where(write, key, jax.lax.dynamic_index_in_dim(line, way, keepdims=False))
    time = jnp.where(write, now, jax.lax.dynamic_index_in_dim(lru_line, way, keepdims=False))
    tags = jax.lax.dynamic_update_slice(tags, tag.reshape(1, 1).astype(jnp.int32), (s, way))
    lru = jax.lax.dynamic_update_slice(lru, time.reshape(1, 1).astype(jnp.int32), (s, way))
    return (tags, lru), hit


def _split_lookup(l1, l2, key, now, fill):
    """L1 then L2; an L1 miss is filled from L2 or from the walk."""
    l1, h1 = _lookup(l1, key, now, False)
    l2, h2 = _lookup(l2, key, now, fill)
    l1, _ = _lookup(l1, key, now, ~h1 & (h2 | fill))
    return l1, l2, h1, h2


def _invalidate(c, keys):
    tags, lru = c
    hit = (tags[:, :, None] == keys[None, None, :]).any(-1)
    return jnp.where(hit, -1, tags), lru


# ---------------------------------------------------------------------------
# The per-access walk
# ---------------------------------------------------------------------------

CYCLE_FIELDS = ("cycles_tlb", "cycles_walk", "cycles_bitmap", "cycles_remap", "cycles_mem")
COUNT_FIELDS = ("miss4_l1", "miss4_l2", "miss2m_l1", "miss2m_l2", "bmc_miss",
                "dram_reads", "dram_writes", "nvm_reads", "nvm_writes")


def _walk(cfg, mach, policy, acc_dtype):
    l1l, l2l = cfg["l1_tlb_lat"], cfg["l2_tlb_lat"]
    f = jnp.float32

    def step(carry, xs):
        tlb4, tlb2m, bmc, t, cyc, cnt = carry
        vpn, sp, dram, wr = xs
        mem = jnp.where(wr, jnp.where(dram, f(mach["t_dw"]), f(mach["t_nw"])),
                        jnp.where(dram, f(mach["t_dr"]), f(mach["t_nr"])))
        if policy == "flat-static":
            a1, a2, h1, h2 = _split_lookup(tlb4[0], tlb4[1], vpn, t, True)
            tlb4 = (a1, a2)
            walk = ~h1 & ~h2
            costs = (f(l1l) + jnp.where(~h1, f(l2l), f(0)),
                     jnp.where(walk, f(mach["walk4"]), f(0)), f(0), f(0), mem)
            events = (~h1, walk, False, False, False)
        else:
            a1, a2, h41, h42 = _split_lookup(tlb4[0], tlb4[1], vpn, t, dram)
            tlb4 = (a1, a2)
            b1, b2, h21, h22 = _split_lookup(tlb2m[0], tlb2m[1], sp, t, True)
            tlb2m = (b1, b2)
            hit4 = (h41 | h42) & dram
            need_bitmap = ~hit4
            bmc, bmc_hit = _lookup(bmc, sp, t, True)
            bmc_miss = need_bitmap & ~bmc_hit
            costs = (
                f(l1l) + jnp.where(~h41 & ~h21, f(l2l), f(0)),
                jnp.where(need_bitmap & ~(h21 | h22), f(mach["walk2m"]), f(0)),
                jnp.where(need_bitmap, f(cfg["bitmap_cache_lat"]) + jnp.where(bmc_miss, f(mach["t_nr"]), f(0)), f(0)),
                jnp.where(need_bitmap & dram, f(mach["remap"]), f(0)),
                mem,
            )
            events = (dram & ~h41, dram & ~hit4, ~h21, ~(h21 | h22), bmc_miss)
        cyc = tuple(c + x.astype(acc_dtype) for c, x in zip(cyc, costs))
        events = events + (dram & ~wr, dram & wr, ~dram & ~wr, ~dram & wr)
        cnt = tuple(c + jnp.asarray(e).astype(jnp.int32) for c, e in zip(cnt, events))
        return (tlb4, tlb2m, bmc, t + 1, cyc, cnt), None

    @jax.jit
    def run(carry, vpn, dram, wr):
        carry, _ = jax.lax.scan(step, carry, (vpn, vpn // PAGES_PER_SP, dram, wr), unroll=8)
        return carry

    return run


# ---------------------------------------------------------------------------
# The interval controller (rainbow)
# ---------------------------------------------------------------------------


def _saturate(counts, add):
    """15-bit counters with a sticky overflow bit; value reads 2**15 once set."""
    value, ovf = counts
    new = value + add
    return jnp.minimum(new, COUNTER_MAX), ovf | (new > COUNTER_MAX)


def _hotness(counts):
    value, ovf = counts
    return jnp.where(ovf, COUNTER_MAX + 1, value)


def _controller(cfg, mach, n_sp):
    top_n, k = int(cfg["top_n"]), int(cfg["max_promotions"])
    slots = mach["dram_slots"]
    a = cfg["t_mig_amortize"]
    f = jnp.float32
    t_nr, t_dr, t_nw, t_dw = f(mach["t_nr"]), f(mach["t_dr"]), f(mach["t_nw"]), f(mach["t_dw"])
    t_mig, t_wb = f(mach["mig_page"] / a), f(mach["writeback_page"] / a)
    ww = int(cfg["write_weight"])
    max_inval = int(cfg["max_invalidate"])

    def benefit(r, w):
        return (t_nr - t_dr) * r + (t_nw - t_dw) * w - t_mig

    @jax.jit
    def interval(st, sp, page, wr, now):
        s1, s2r, s2w, psn, slot, migrated, dram, threshold = st
        # observe: NVM accesses count per superpage (writes weigh more) and,
        # in monitored superpages, per page; DRAM accesses count per slot
        in_dram = migrated[sp, page]
        nvm = ~in_dram
        s1 = _saturate(s1, jnp.zeros(n_sp, jnp.int32).at[sp].add(jnp.where(nvm, jnp.where(wr, ww, 1), 0)))
        row_eq = (sp[:, None] == psn[None, :]) & (psn[None, :] >= 0)
        row = jnp.where(row_eq.any(1) & nvm, jnp.argmax(row_eq, 1), top_n)
        s2r = _saturate(s2r, jnp.zeros((top_n + 1, PAGES_PER_SP), jnp.int32).at[row, page].add((~wr).astype(jnp.int32))[:top_n])
        s2w = _saturate(s2w, jnp.zeros((top_n + 1, PAGES_PER_SP), jnp.int32).at[row, page].add(wr.astype(jnp.int32))[:top_n])
        state, d_sp, d_page, d_r, d_w, touch = dram
        s = jnp.where(in_dram, slot[sp, page], slots)
        d_r = d_r.at[s].add(jnp.where(wr, 0.0, 1.0), mode="drop")
        d_w = d_w.at[s].add(jnp.where(wr, 1.0, 0.0), mode="drop")
        state = state.at[s].max(jnp.where(wr, DIRTY, FREE), mode="drop")
        touch = touch.at[s].max(now, mode="drop")

        # candidates: the K best monitored pages not already in DRAM
        reads, writes = _hotness(s2r).astype(f), _hotness(s2w).astype(f)
        valid_row = psn >= 0
        resident = migrated[jnp.maximum(psn, 0)]
        score = jnp.where(valid_row[:, None] & ~resident, benefit(reads, writes), -jnp.inf).reshape(-1)
        order = jnp.argsort(-score, stable=True)[:k]
        c_ok = score[order] > -jnp.inf
        c_sp = jnp.where(c_ok, psn[order // PAGES_PER_SP], -1)
        c_page = (order % PAGES_PER_SP).astype(jnp.int32)
        c_r, c_w = reads.reshape(-1)[order], writes.reshape(-1)[order]

        # admission: best candidate first into the cheapest victim
        # (free, then clean, then dirty; least recently touched first)
        base = jnp.where(c_sp >= 0, benefit(c_r, c_w), -jnp.inf)
        best = jnp.argsort(-base, stable=True)
        prio = state.astype(f) * f(1e9) + touch.astype(f)
        victim = jnp.argsort(prio, stable=True)[:k]
        v_state, v_r, v_w = state[victim], d_r[victim], d_w[victim]
        v_free, v_dirty = v_state == FREE, v_state == DIRTY
        b_sp, b_page, b_r, b_w = c_sp[best], c_page[best], c_r[best], c_w[best]
        swap = ((t_nr - t_dr) * (b_r - v_r) + (t_nw - t_dw) * (b_w - v_w) - t_mig
                - jnp.where(v_dirty, t_wb, f(0)))
        adj = jnp.where(v_free, base[best], swap)
        go = (adj > threshold) & (b_sp >= 0)
        ev = go & ~v_free
        ev_sp = jnp.where(ev, d_sp[victim], -1)
        ev_page = jnp.where(ev, d_page[victim], -1)
        n_mig, n_ev = go.sum(), ev.sum()
        n_dirty = (ev & v_dirty).sum()

        # commit: evict then install in the tables and the slots
        drop_sp = jnp.where(ev, ev_sp, n_sp)
        migrated = migrated.at[drop_sp, ev_page].set(False, mode="drop")
        slot = slot.at[drop_sp, ev_page].set(-1, mode="drop")
        in_sp = jnp.where(go, b_sp, n_sp)
        migrated = migrated.at[in_sp, b_page].set(True, mode="drop")
        slot = slot.at[in_sp, b_page].set(victim, mode="drop")
        dst = jnp.where(go, victim, slots)
        state = state.at[dst].set(CLEAN, mode="drop")
        d_sp = d_sp.at[dst].set(b_sp, mode="drop")
        d_page = d_page.at[dst].set(b_page, mode="drop")
        touch = touch.at[dst].set(now, mode="drop")

        # shootdowns: the first `max_inval` evicted pages in candidate order
        inv = jnp.zeros(k, jnp.int32).at[best].set(jnp.arange(k, dtype=jnp.int32))
        ev_vpn = (ev_sp * PAGES_PER_SP + ev_page)[inv]
        ev_ok = ev[inv]
        pos = jnp.where(ev_ok, jnp.cumsum(ev_ok) - 1, max_inval)
        inval = jnp.full(max_inval + 1, -1, jnp.int32).at[pos].set(ev_vpn, mode="drop")[:max_inval]

        # next interval monitors this interval's top-N superpages
        hot = _hotness(s1)
        vals, idx = jax.lax.top_k(hot, min(top_n, n_sp))
        new_psn = jnp.full(top_n, -1, jnp.int32).at[: vals.shape[0]].set(jnp.where(vals > 0, idx, -1))
        zero = (jnp.zeros(n_sp, jnp.int32), jnp.zeros(n_sp, bool))
        zero2 = (jnp.zeros((top_n, PAGES_PER_SP), jnp.int32), jnp.zeros((top_n, PAGES_PER_SP), bool))
        threshold = jnp.clip(threshold * f(0.9) + f(8.0) * n_ev.astype(f), 0.0, 1e6)
        dram = (state, d_sp, d_page, jnp.zeros_like(d_r), jnp.zeros_like(d_w), touch)
        new = (zero, zero2, zero2, new_psn, slot, migrated, dram, threshold)
        return new, (n_mig, n_ev, n_dirty), inval

    def init():
        zero = (jnp.zeros(n_sp, jnp.int32), jnp.zeros(n_sp, bool))
        zero2 = (jnp.zeros((top_n, PAGES_PER_SP), jnp.int32), jnp.zeros((top_n, PAGES_PER_SP), bool))
        dram = (jnp.zeros(slots, jnp.int32), jnp.full(slots, -1, jnp.int32), jnp.full(slots, -1, jnp.int32),
                jnp.zeros(slots, f), jnp.zeros(slots, f), jnp.zeros(slots, jnp.int32))
        return (zero, zero2, zero2, jnp.full(top_n, -1, jnp.int32),
                jnp.full((n_sp, PAGES_PER_SP), -1, jnp.int32),
                jnp.zeros((n_sp, PAGES_PER_SP), bool), dram, f(cfg["mig_threshold"]))

    return init, interval


# ---------------------------------------------------------------------------
# Whole simulation
# ---------------------------------------------------------------------------


def simulate(cfg: dict, policy: str, seed: int, intervals: int,
             accesses: int | None = None, acc_dtype=jnp.float32) -> dict:
    """The metrics of one simulation, keyed as the program's SimMetrics row."""
    if policy not in ("flat-static", "rainbow"):
        raise ValueError(f"the reference simulates flat-static and rainbow, not {policy!r}")
    accesses = int(accesses or cfg["accesses_per_interval"])
    mach = machine(cfg)
    shape = workload_shape(cfg)
    n_sp = shape["num_superpages"]
    hot = _hot_set(cfg, seed)
    walk = _walk(cfg, mach, policy, acc_dtype)
    c4 = (_cache(cfg["l1_tlb_entries"], cfg["l1_tlb_ways"]), _cache(cfg["l2_tlb_entries"], cfg["l2_tlb_ways"]))
    c2 = (_cache(cfg["l1_tlb_entries"], cfg["l1_tlb_ways"]), _cache(cfg["l2_tlb_entries"], cfg["l2_tlb_ways"]))
    bmc = _cache(cfg["bitmap_cache_entries"], cfg["bitmap_cache_ways"])
    carry = (c4, c2, bmc, jnp.int32(0),
             tuple(jnp.zeros((), acc_dtype) for _ in CYCLE_FIELDS),
             tuple(jnp.zeros((), jnp.int32) for _ in COUNT_FIELDS))
    dram_share = cfg["dram_bytes"] / (cfg["dram_bytes"] + cfg["nvm_bytes"])
    init, interval = _controller(cfg, mach, n_sp)
    st = init()
    moves = []
    for i in range(intervals):
        pages, wr = interval_trace(cfg, hot, seed, i, accesses)
        sp, page = pages // PAGES_PER_SP, pages % PAGES_PER_SP
        if policy == "flat-static":
            h = (np.asarray(pages, np.int64) * FLAT_HASH_KNUTH) % FLAT_HASH_MOD
            dram = jnp.asarray(h < int(FLAT_HASH_MOD * dram_share))
        else:
            dram = st[5][sp, page]
        carry = walk(carry, pages, dram, wr)
        if policy == "rainbow":
            st, m, inval = interval(st, sp, page, wr, jnp.int32(i))
            (l1, l2) = carry[0]
            carry = ((_invalidate(l1, inval), _invalidate(l2, inval)),) + carry[1:]
            moves.append(tuple(int(x) for x in m))
        else:
            moves.append((0, 0, 0))
    cyc = {k: float(v) for k, v in zip(CYCLE_FIELDS, carry[4])}
    cnt = {k: float(v) for k, v in zip(COUNT_FIELDS, carry[5])}
    return finalize(cfg, mach, policy, cyc, cnt, moves, accesses, shape["footprint_pages"])


def finalize(cfg, mach, policy, cyc, cnt, moves, accesses, footprint_pages) -> dict:
    """Host totals in float64: instructions, cycles, MPKI, traffic, energy."""
    mig_bytes = mig_cycles = shoot_cycles = flush_cycles = 0.0
    migrations = evictions = 0
    for m, e, d in moves:
        migrations += m
        evictions += e
        if policy == "rainbow":
            mig_bytes += m * 4096.0 + d * 4096.0 + (e - d) * 8.0
            mig_cycles += m * mach["mig_page"] + d * mach["writeback_page"]
            shoot_cycles += e * cfg["shootdown_cost"]
            flush_cycles += (m + e) * (4096 / cfg["line_bytes"]) * cfg["clflush_per_line"]
    instructions = accesses * len(moves) * cfg["inst_per_access"]
    trans = cyc["cycles_tlb"] + cyc["cycles_walk"] + cyc["cycles_bitmap"] + cyc["cycles_remap"]
    total = (instructions * cfg["base_cpi"] + trans + cyc["cycles_mem"]
             + mig_cycles + shoot_cycles + flush_cycles)
    misses = cnt["miss4_l2"] if policy == "flat-static" else cnt["miss2m_l2"]
    fp_bytes = footprint_pages * 4096.0
    out = {
        "instructions": instructions, "total_cycles": total, "ipc": instructions / total,
        "mpki": misses / (instructions / 1000.0), "tlb_service_cycles": trans,
        "tlb_service_frac": trans / total, **cyc,
        "cycles_mig": mig_cycles, "cycles_shootdown": shoot_cycles,
        "cycles_clflush": flush_cycles, "bmc_misses": cnt["bmc_miss"],
        "migrations": float(migrations), "evictions": float(evictions),
        "shootdowns": float(evictions), "mig_bytes": mig_bytes,
        "footprint_bytes": fp_bytes, "traffic_ratio": mig_bytes / fp_bytes,
        # the flat timing model charges no queueing and no aborts
        "cycles_bank_stall": 0.0, "bank_stall_cycles": 0.0, "mig_stall_cycles": 0.0,
        "queue_occupancy_dram": 0.0, "queue_occupancy_nvm": 0.0, "mig_aborts": 0.0,
    }
    out.update({f"energy_{k}": v for k, v in energy(cfg, cnt, mig_bytes, total).items()})
    return out


def energy(cfg, cnt, mig_bytes, total_cycles) -> dict:
    """Table IV: DRAM current x voltage x latency, PCM pJ per bit, static
    standby + refresh over the wall time; scaled work is scaled back up."""
    ghz, scale = cfg["cpu_ghz"], cfg["scale_down"]
    e_dr = cfg["dram_volt"] * (cfg["dram_read_ma"] * 1e-3) * (cfg["t_dram_read_ns"] * ghz / (ghz * 1e9))
    e_dw = cfg["dram_volt"] * (cfg["dram_write_ma"] * 1e-3) * (cfg["t_dram_write_ns"] * ghz / (ghz * 1e9))
    bits = cfg["line_bytes"] * 8
    e_nr = cfg["pcm_read_pj_bit"] * bits * 1e-12
    e_nw = cfg["pcm_write_pj_bit"] * bits * 1e-12
    dyn = (cnt["dram_reads"] * e_dr + cnt["dram_writes"] * e_dw
           + cnt["nvm_reads"] * e_nr + cnt["nvm_writes"] * e_nw) * scale
    mig = mig_bytes / cfg["line_bytes"] * (e_nr + e_dw) * scale
    wall = total_cycles * scale / (ghz * 1e9)
    static = cfg["dram_volt"] * (cfg["dram_standby_ma"] + cfg["dram_refresh_ma"]) * 1e-3 * wall
    return {"dynamic_j": dyn, "migration_j": mig, "static_j": static,
            "total_j": dyn + mig + static}


def rel_gap(a: float, b: float) -> float:
    """|a - b| relative to |b|; 0 when both are 0."""
    if a == b:
        return 0.0
    return abs(a - b) / max(abs(b), math.ulp(0.0))
