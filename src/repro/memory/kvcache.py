"""RainbowKVCache: the paper's two-tier page management applied to KV caches.

Mapping (DESIGN.md §2): per-sequence KV is stored in a *capacity pool* (the NVM
analogue — host DRAM on a real deployment) at superblock granularity; hot KV
blocks are cached in a small *hot pool* (the DRAM analogue — HBM). A residency
bitmap + remap table (core.remap) redirect block reads; superblocks are never
re-laid-out, so promotion/demotion never touches the block table (the
"no-splinter / no-shootdown" property).

"Access" = attention mass a block receives during decode (strictly more precise
than the paper's post-LLC reference counts — adaptation note 3). Two-stage
counting (core.counting) runs at superblock then block granularity; admission is
the utility test (core.migration) with (HBM bw, host-link bw) timings.

Translation yields one table per step: each block's capacity-pool home, or
its hot-pool slot when resident (serving/rainbow_decode.pool_indices). On a
TPU, kernels/rainbow_attention follows it block by block, DMAing only the
live blocks of a layer from the stacked pool each lives in; the pure-JAX read
path gathers through the layer's concatenated [capacity ++ hot] pool.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import counting, migration
from repro.core.migration import TimingParams, preset_timing
from repro.core.remap import RemapState, remap_init, translate
from repro.engine.policy import ControlPolicy, get_policy
from repro.utils import pytree_dataclass, static_field


@pytree_dataclass(init=False)
class PagedConfig:
    """Layer-B cache config: ControlPolicy + block-pool geometry.

    The interval-controller knobs (`hot_slots`, `top_n`, `max_promotions`,
    `interval_steps`, ...) live on `policy` — the same ControlPolicy surface
    Layer A's RainbowConfig composes and engine.autotune searches over. The
    pre-redesign flat kwargs are kept as deprecation shims (init kwargs +
    read-only properties), so `PagedConfig(hot_slots=8, ...)` and
    `dataclasses.replace(pcfg, interval_steps=2)` keep working.
    """

    block_size: int = static_field(default=16)  # tokens per block (4KB-page analogue)
    blocks_per_seq: int = static_field(default=512)  # blocks per superblock run
    quantize: bool = static_field(default=False)  # int8 pools + bf16 scales
                                                  # (beyond-paper §Perf A3)
    policy: ControlPolicy = static_field(default=None)

    def __init__(
        self,
        block_size: int = 16,
        blocks_per_seq: int = 512,
        hot_slots: int | None = None,
        top_n: int | None = None,
        max_promotions: int | None = None,
        interval_steps: int | None = None,
        quantize: bool = False,
        policy: ControlPolicy | str | None = None,
    ):
        if policy is None:
            policy = get_policy("serving-default")
        elif isinstance(policy, str):
            policy = get_policy(policy)
        legacy = {
            "hot_slots": hot_slots,
            "top_n": top_n,
            "max_promotions": max_promotions,
            "interval_steps": interval_steps,
        }
        overrides = {k: v for k, v in legacy.items() if v is not None}
        if overrides:
            policy = dataclasses.replace(policy, **overrides)
        object.__setattr__(self, "block_size", block_size)
        object.__setattr__(self, "blocks_per_seq", blocks_per_seq)
        object.__setattr__(self, "quantize", quantize)
        object.__setattr__(self, "policy", policy.validate("PagedConfig"))
        self.validate()

    def validate(self) -> "PagedConfig":
        """Reject impossible serving geometries loudly (satellite fix) — the
        old flat config let these flow into engine.control and silently
        miscount (e.g. stage-2 monitor rows wider than the superblock)."""
        pol = self.policy
        if self.block_size < 1 or self.blocks_per_seq < 1:
            raise ValueError(
                "PagedConfig: block_size and blocks_per_seq must be >= 1 "
                f"(got {self.block_size}, {self.blocks_per_seq})"
            )
        if pol.top_n > self.blocks_per_seq:
            # Conservative guard: top_n counts monitored stage-2 units
            # (sequences), and each monitor row carries blocks_per_seq
            # counters — a top_n beyond the per-sequence block count is
            # almost always a swapped or mistyped knob, so fail loudly.
            raise ValueError(
                f"PagedConfig: top_n ({pol.top_n}) > blocks_per_seq "
                f"({self.blocks_per_seq}) — each stage-2 monitor row holds "
                "blocks_per_seq counters; a monitor table wider than one "
                "superblock's block count is a mis-sized config (shrink "
                "top_n or pass a larger blocks_per_seq)"
            )
        if pol.max_promotions > pol.hot_slots:
            raise ValueError(
                f"PagedConfig: max_promotions ({pol.max_promotions}) > "
                f"hot_slots ({pol.hot_slots}) — one interval can never admit "
                "more blocks than the hot pool holds"
            )
        return self

    # -- deprecation shims (old flat-knob surface) --------------------------

    @property
    def hot_slots(self) -> int:
        return self.policy.hot_slots

    @property
    def top_n(self) -> int:
        return self.policy.top_n

    @property
    def max_promotions(self) -> int:
        return self.policy.max_promotions

    @property
    def interval_steps(self) -> int:
        return self.policy.interval_steps


def default_timing() -> TimingParams:
    """The "v5e-serving" preset of core.migration.TIMING_PRESETS (ns-per-block
    HBM vs host-link costs) — one shared table with the simulator's machine
    model instead of a second hand-maintained copy."""
    return preset_timing("v5e-serving")


@pytree_dataclass
class RainbowKV:
    """Per-layer-stacked paged KV state for a decode batch.

    cap_k/cap_v: [L, B*blocks_per_seq, block, KVS, hd]  capacity pool
    hot_k/hot_v: [L, hot_slots, block, KVS, hd]         hot pool
                 For multi-head latent attention (cfg.mla) there is one
                 latent pool per tier: cap_k/hot_k [L, n, block, 1,
                 cfg.latent_width] hold each token's row [c ++ k_rope ++
                 zeros], and cap_v/hot_v are None.
    remap:       RemapState over (superblock=seq, page=block) — shared by layers
                 (hotness is measured summed over layers; per-layer remap is a
                 config away but multiplies table traffic for little gain)
    s1/s2:       two-stage counters (stage 1 per superblock, stage 2 per block)
    dram:        hot-pool slot manager (free/clean/dirty; KV blocks are clean)
    length:      int32 current sequence length (uniform across batch)
    step_in_interval: int32
    """

    cap_k: jax.Array
    cap_v: jax.Array
    hot_k: jax.Array
    hot_v: jax.Array
    remap: RemapState
    s1: counting.Stage1State
    s2: counting.Stage2State
    dram: migration.DramState
    threshold: jax.Array
    length: jax.Array
    step_in_interval: jax.Array


def paged_init(cfg, pcfg: PagedConfig, batch: int, tp: int, layers: int) -> RainbowKV:
    if cfg.mla:
        if pcfg.quantize:
            raise NotImplementedError("the int8 pools hold per-head K/V, not latent rows")
        kvs, hd = 1, cfg.latent_width
    else:
        kvs, hd = cfg.kv_store(tp), cfg.head_dim
    dt = jnp.int8 if pcfg.quantize else jnp.dtype(cfg.dtype)
    nb = batch * pcfg.blocks_per_seq
    shape_cap = (layers, nb, pcfg.block_size, kvs, hd)
    shape_hot = (layers, pcfg.hot_slots, pcfg.block_size, kvs, hd)
    kv = RainbowKV(
        cap_k=jnp.zeros(shape_cap, dt),
        cap_v=None if cfg.mla else jnp.zeros(shape_cap, dt),
        hot_k=jnp.zeros(shape_hot, dt),
        hot_v=None if cfg.mla else jnp.zeros(shape_hot, dt),
        remap=remap_init(batch, pcfg.blocks_per_seq),
        s1=counting.stage1_init(batch),
        s2=counting.stage2_init(pcfg.top_n, pcfg.blocks_per_seq),
        dram=migration.dram_init(pcfg.hot_slots),
        threshold=jnp.asarray(pcfg.policy.threshold_init, jnp.float32),
        length=jnp.zeros((), jnp.int32),
        step_in_interval=jnp.zeros((), jnp.int32),
    )
    return kv


def paged_scales_init(pcfg: PagedConfig, batch: int, kvs: int, layers: int):
    """int8 mode: per-token, per-kv-head scale side pytree (1 fp32 per head_dim
    payload — 1/64 the pool bytes at hd=128 with fp32 scales)."""
    nb = batch * pcfg.blocks_per_seq
    return {
        "cap_k": jnp.zeros((layers, nb, pcfg.block_size, kvs), jnp.float32),
        "cap_v": jnp.zeros((layers, nb, pcfg.block_size, kvs), jnp.float32),
        "hot_k": jnp.zeros((layers, pcfg.hot_slots, pcfg.block_size, kvs), jnp.float32),
        "hot_v": jnp.zeros((layers, pcfg.hot_slots, pcfg.block_size, kvs), jnp.float32),
    }


def quantize_kv(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """x: [..., hd] -> (int8[..., hd], scale[...]) per-channel symmetric."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) + 1e-8
    scale = amax / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale


def dequantize_kv(q: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def paged_cache_specs(batch_axes="data", model_axis="model") -> RainbowKV:
    """PartitionSpec tree matching paged_init's structure (for pjit shardings).

    Capacity pools shard over the flattened (seq x block) dim (batch-major) and
    kv-head slots; hot pools shard kv-heads only (the hot set is a global
    resource); tables/counters are tiny and replicate.
    """
    from jax.sharding import PartitionSpec as P

    from repro.core.counting import Stage1State, Stage2State
    from repro.core.migration import DramState

    cap = P(None, batch_axes, None, model_axis, None)
    hot = P(None, None, None, model_axis, None)
    return RainbowKV(
        cap_k=cap, cap_v=cap, hot_k=hot, hot_v=hot,
        remap=RemapState(bitmap=P(None, None), remap=P(None, None)),
        s1=Stage1State(counts=P(None)),
        s2=Stage2State(psn=P(None), counts=P(None, None)),
        dram=DramState(*([P(None)] * 6)),
        threshold=P(), length=P(), step_in_interval=P(),
    )


def block_of(pcfg: PagedConfig, pos: jax.Array) -> jax.Array:
    return pos // pcfg.block_size


def append_token(
    kv: RainbowKV, pcfg: PagedConfig, layer_slice: None, k_new: jax.Array, v_new: jax.Array
) -> RainbowKV:
    """Write one token's K/V into the capacity pool (all layers at once).

    k_new/v_new: [L, B, KVS, hd]. New tokens go to their home capacity block —
    DRAM-preferred placement happens via promotion (fresh blocks are usually
    hot and get promoted at the next interval). With latent pools, k_new is
    the latent row and v_new is None.
    """
    lyr, b, kvs, hd = k_new.shape
    pos = kv.length
    blk = pos // pcfg.block_size
    off = pos % pcfg.block_size
    seq_ids = jnp.arange(b)
    flat_block = seq_ids * pcfg.blocks_per_seq + blk  # [B]
    if v_new is None:
        return _append_latent(kv, seq_ids, blk, flat_block, off, k_new)
    cap_k = kv.cap_k.at[:, flat_block, off].set(k_new.astype(kv.cap_k.dtype))
    cap_v = kv.cap_v.at[:, flat_block, off].set(v_new.astype(kv.cap_v.dtype))
    # Paper §III-E cases 1/2: writes to a migrated page must land on the fast
    # copy too, else reads through the remap see stale data. (We also keep the
    # capacity copy fresh, so evictions are always "clean" — KV blocks never
    # pay T_writeback; exactly the clean-list fast path the paper optimizes.)
    resident, slot = translate(kv.remap, seq_ids, jnp.full((b,), blk))
    slot_safe = jnp.where(resident, slot, kv.hot_k.shape[1])  # OOB -> dropped
    hot_k = kv.hot_k.at[:, slot_safe, off].set(
        k_new.astype(kv.hot_k.dtype), mode="drop"
    )
    hot_v = kv.hot_v.at[:, slot_safe, off].set(
        v_new.astype(kv.hot_v.dtype), mode="drop"
    )
    return _replace(kv, cap_k=cap_k, cap_v=cap_v, hot_k=hot_k, hot_v=hot_v)


def _append_latent(kv: RainbowKV, seq_ids, blk, flat_block, off, row) -> RainbowKV:
    """append_token for latent pools: each sequence's current block is read,
    the row at `off` replaced, and the whole block written back. A latent
    block's (token, lane) plane is the tiled one, so a one-row scatter would
    make XLA lay the whole pool out again (twice per step); whole blocks
    keep its layout. Resident blocks get the row in their hot slot too."""

    def write(pool, idx):
        cur = pool.at[:, idx].get(mode="clip")  # [L, B, block, 1, W]
        here = (jnp.arange(pool.shape[2]) == off)[None, None, :, None, None]
        new = jnp.where(here, row[:, :, None].astype(pool.dtype), cur)
        return pool.at[:, idx].set(new, mode="drop")

    resident, slot = translate(kv.remap, seq_ids, jnp.full(seq_ids.shape, blk))
    slot_safe = jnp.where(resident, slot, kv.hot_k.shape[1])  # OOB -> dropped
    return _replace(kv, cap_k=write(kv.cap_k, flat_block), hot_k=write(kv.hot_k, slot_safe))


def append_token_q8(
    kv: RainbowKV, pcfg: PagedConfig, scales: dict, k_new: jax.Array, v_new: jax.Array
) -> tuple[RainbowKV, dict]:
    """int8-mode append: quantize per (layer, seq, kv-head), write pools+scales."""
    lyr, b, kvs, hd = k_new.shape
    pos = kv.length
    blk = pos // pcfg.block_size
    off = pos % pcfg.block_size
    seq_ids = jnp.arange(b)
    flat_block = seq_ids * pcfg.blocks_per_seq + blk
    qk, sk = quantize_kv(k_new)
    qv, sv = quantize_kv(v_new)
    cap_k = kv.cap_k.at[:, flat_block, off].set(qk)
    cap_v = kv.cap_v.at[:, flat_block, off].set(qv)
    scales = dict(scales)
    scales["cap_k"] = scales["cap_k"].at[:, flat_block, off].set(sk)
    scales["cap_v"] = scales["cap_v"].at[:, flat_block, off].set(sv)
    # mirror writes into promoted blocks (paper case 1/2, as in append_token)
    resident, slot = translate(kv.remap, seq_ids, jnp.full((b,), blk))
    slot_safe = jnp.where(resident, slot, kv.hot_k.shape[1])
    hot_k = kv.hot_k.at[:, slot_safe, off].set(qk, mode="drop")
    hot_v = kv.hot_v.at[:, slot_safe, off].set(qv, mode="drop")
    scales["hot_k"] = scales["hot_k"].at[:, slot_safe, off].set(sk, mode="drop")
    scales["hot_v"] = scales["hot_v"].at[:, slot_safe, off].set(sv, mode="drop")
    return _replace(kv, cap_k=cap_k, cap_v=cap_v, hot_k=hot_k, hot_v=hot_v), scales


def promote_scales(scales: dict, pcfg: PagedConfig, plan, cand_sp, cand_pg) -> dict:
    """Mirror end_interval_promote's block copies on the scale side pytree."""
    src = jnp.where(plan.migrate, cand_sp * pcfg.blocks_per_seq + cand_pg, 0).astype(jnp.int32)
    dst = jnp.where(plan.migrate, plan.dst_slot, pcfg.hot_slots).astype(jnp.int32)
    out = dict(scales)
    out["hot_k"] = scales["hot_k"].at[:, dst].set(scales["cap_k"][:, src], mode="drop")
    out["hot_v"] = scales["hot_v"].at[:, dst].set(scales["cap_v"][:, src], mode="drop")
    return out


def _replace(kv: RainbowKV, **kw) -> RainbowKV:
    return dataclasses.replace(kv, **kw)


def quantize_mass(mass: jax.Array) -> jax.Array:
    """Attention mass -> uint32 access counts for the 15-bit counters.

    THE single quantization of Layer B's access stream: observe_block_mass
    counts with it and engine.autotune's replay prices the same counts, so the
    tuner's cost model scores exactly the stream the controller sees.
    """
    return jnp.clip(mass * 64.0, 0, 1024).astype(jnp.uint32)


def observe_block_mass(
    kv: RainbowKV, pcfg: PagedConfig, mass: jax.Array
) -> RainbowKV:
    """Record per-block attention mass for this decode step.

    mass: float32[B, blocks_per_seq] — summed softmax mass per KV block
    (aggregated over layers/heads by the caller). Quantized to integer counts
    for the paper's 15-bit counters; the floor of 1 keeps every monitored
    block's counter warm. NOTE: this deliberately CHANGES the pre-refactor
    accounting, which computed the extra weight as uint32 `(q - 1).clip(0)` —
    at q = 0 that underflows to 2^32-1 and saturated zero-mass blocks straight
    to "definitely hot", letting cold blocks win promotions. max(q, 1) is the
    intended semantics.
    """
    b, nblk = mass.shape
    q = quantize_mass(mass)
    seq_ids = jnp.arange(b, dtype=jnp.int32)
    s1 = counting.stage1_record_weighted(kv.s1, seq_ids, q.sum(axis=1))
    # stage 2: only monitored superblocks count at block grain, mass-weighted
    flat_sp = seq_ids[:, None].repeat(nblk, 1).reshape(-1)
    flat_pg = jnp.arange(nblk, dtype=jnp.int32)[None].repeat(b, 0).reshape(-1)
    s2 = counting.stage2_record_weighted(
        kv.s2, flat_sp, flat_pg, jnp.maximum(q.reshape(-1), 1)
    )
    return _replace(kv, s1=s1, s2=s2, step_in_interval=kv.step_in_interval + 1)


def end_interval_promote(
    kv: RainbowKV, pcfg: PagedConfig, timing: TimingParams | None = None
) -> tuple[RainbowKV, dict]:
    """Close the interval: pick hot blocks (two-stage), admit into the hot pool
    (utility test), copy block payloads, update remap.

    Layer B's end-interval IS the engine controller: candidate extraction,
    Eq. 1/2 admission, remap evict+install, threshold adaptation, and monitor
    rotation all run through repro.engine.control (the same code Layer A's
    rainbow.end_interval composes); only the block payload copy onto the KV
    pools is serving-specific.
    """
    from repro.engine import control

    timing = timing or default_timing()
    b = kv.s1.counts.shape[0]
    # the controller instance comes straight from the unified policy surface
    ctrl = pcfg.policy.control_config(
        num_units=b, pages_per_unit=pcfg.blocks_per_seq
    )
    reads = counting.counter_value(kv.s2.counts)
    # never promote blocks beyond the current sequence length
    out_of_range = (
        jnp.arange(pcfg.blocks_per_seq, dtype=jnp.int32)[None, :]
        > (kv.length // pcfg.block_size)
    )
    out = control.plan_and_apply(
        ctrl, reads, jnp.zeros_like(reads), kv.s2.psn,
        kv.remap, kv.dram, kv.threshold, timing, now=jnp.int32(0),
        extra_exclude=jnp.broadcast_to(out_of_range, reads.shape),
    )
    plan, cand_sp, cand_pg = out.plan, out.cand_sp, out.cand_page

    # ---- block payload copies (the block_gather kernel's reference path) ----
    src = jnp.where(
        plan.migrate, cand_sp * pcfg.blocks_per_seq + cand_pg, 0
    ).astype(jnp.int32)
    # invalid lanes scatter out of bounds and are dropped (no slot-0 races)
    dst = jnp.where(plan.migrate, plan.dst_slot, pcfg.hot_slots).astype(jnp.int32)
    gathered_k = kv.cap_k[:, src]  # [L, K, block, KVS, hd]
    gathered_v = None if kv.cap_v is None else kv.cap_v[:, src]  # None: latent pools
    hot_k = kv.hot_k.at[:, dst].set(gathered_k, mode="drop")
    hot_v = None if gathered_v is None else kv.hot_v.at[:, dst].set(gathered_v, mode="drop")

    s1, new_psn, dram = control.rotate_monitors(ctrl, kv.s1, out.dram)
    new = _replace(
        kv,
        hot_k=hot_k, hot_v=hot_v, remap=out.remap, dram=dram,
        s1=s1,
        s2=counting.stage2_begin(new_psn, pcfg.blocks_per_seq),
        threshold=out.threshold,
        step_in_interval=jnp.zeros((), jnp.int32),
    )
    return new, {"promoted": out.n_migrated, "evicted": out.n_evicted,
                 "plan": plan, "cand_sp": cand_sp, "cand_pg": cand_pg}
