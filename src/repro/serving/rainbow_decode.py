"""Rainbow-managed decode: paged KV with two-tier translation + hot-block stats.

Read modes:
  * full   — attend over the whole history through the translation. On a TPU
             with float pools and blocks on the bf16 (16, 128) tile, the
             kernels/rainbow_attention kernel DMAs only the live blocks, each
             from the pool it lives in; elsewhere one gather through the
             layer's concatenated [capacity ++ hot] pool reads every block
             and masks past the length (numerically identical to flat-cache
             decode).
  * sparse — attend over hot-pool blocks + the trailing window only (stage-1
             screened). This is where tiering pays on real hardware: cold
             blocks stay in the capacity tier (host memory) untouched. The
             approximation (H2O/Quest-style) is opt-in; any block whose mass
             grows gets promoted and rejoins the read set.

Latent-attention (MLA) models read one latent row per token from a single
pool per tier, in full mode only (`_mla_layers`; docs/paged_decode.md).

Each decode step records per-block attention mass (the access stream of the
paper's memory controller); every `interval_steps`, end_interval_promote() runs
two-stage classification + utility admission and copies hot blocks.

The interval control loop here is the SAME engine as Layer A's simulator:
observe_block_mass feeds the shared weighted stage-1/2 counters and
end_interval_promote plans through repro.engine.control.plan_and_apply — only
the access semantics (attention mass) and the payload copy differ.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.remap import translate
from repro.kernels.rainbow_attention import ops as ra_ops
from repro.memory.kvcache import (
    PagedConfig,
    RainbowKV,
    append_token,
    append_token_q8,
    dequantize_kv,
    end_interval_promote,
    observe_block_mass,
    paged_init,
    promote_scales,
)
from repro.models import attention as attn
from repro.models import layers as L
from repro.models import mla
from repro.models import model as M
from repro.models import moe as moe_mod


def _attend_with_mass(q, k, v, valid, block_size, nblk):
    """decode_attend that also returns per-block softmax mass [B, nblk].

    valid: bool[S] or bool[B, S] mask of readable positions.
    """
    b, smax, kvs, hd = k.shape
    hp = q.shape[2]
    ke = attn._expand_kv(k, hp)
    ve = attn._expand_kv(v, hp)
    s = jnp.einsum("bqhk,bshk->bhqs", q, ke, preferred_element_type=jnp.float32)
    s = s / np.sqrt(hd)
    if valid.ndim == 1:
        valid = valid[None]
    s = jnp.where(valid[:, None, None, :], s, attn.NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum(
        "bhqs,bshk->bqhk", p.astype(q.dtype), ve, preferred_element_type=jnp.float32
    ).astype(q.dtype)
    mass = p[:, :, 0, :].sum(axis=1)  # [B, S] summed over heads
    full = nblk * block_size
    blk_mass = mass[:, :full].reshape(b, nblk, block_size).sum(-1)
    return out, blk_mass


def pool_indices(kv: RainbowKV, pcfg: PagedConfig, batch: int):
    """Layer-invariant translated pool indices: (resident[B, nblk], vidx[B, nblk]).

    vidx indexes a virtual [capacity ++ hot] pool: a block's capacity home,
    or num_cap + slot when resident (Fig. 6 cases via one indirection). The
    kernel path reads each block from the pool vidx names; the jnp path
    gathers through the concatenation.
    """
    nblk = pcfg.blocks_per_seq
    blocks = jnp.arange(nblk)
    sp = jnp.arange(batch)[:, None].repeat(nblk, 1)
    resident, slot = translate(kv.remap, sp, blocks[None, :].repeat(batch, 0))
    home = (sp * nblk + blocks[None, :]).astype(jnp.int32)
    vidx = jnp.where(resident, batch * nblk + slot, home)  # [B, nblk]
    return resident, vidx


def sparse_read_set(
    kv: RainbowKV,
    pcfg: PagedConfig,
    batch: int,
    nwin: int = 8,
    precomputed: tuple[jax.Array, jax.Array] | None = None,
):
    """Sparse-mode read set: trailing-window home blocks ++ resident blocks.

    Returns (read_idx, read_valid, read_block): pool indices per read lane,
    the lane validity mask, and the seq-local block id each lane reads (-1 on
    invalid lanes). This is the promotion-rejoin surface: a cold block whose
    attention mass grows gets admitted by end_interval_promote, becomes
    resident, and re-enters this set on the next decode step.
    """
    resident, vidx = precomputed or pool_indices(kv, pcfg, batch)
    nblk = pcfg.blocks_per_seq
    cur_blk = kv.length // pcfg.block_size
    win = jnp.clip((cur_blk - jnp.arange(nwin))[None, :].repeat(batch, 0), 0, nblk - 1)
    win_idx = jnp.take_along_axis(vidx, win, axis=1)
    # Every block must appear in the read set at most ONCE: a duplicated key
    # does not split its softmax mass, it DOUBLES its share (both copies add
    # exp(s) to the numerator and denominator), skewing both the attention
    # output and the recorded per-block mass. Window lanes dedupe against
    # earlier window lanes (edge clipping repeats blocks early in decode)...
    lane = jnp.arange(nwin)
    win_dup = (win[:, :, None] == win[:, None, :]) & (lane[:, None] > lane[None, :])
    win_valid = ~win_dup.any(-1)
    # ...and hot lanes dedupe against the window. The hot pool is a GLOBAL
    # resource: one sequence may own every slot, so each sequence exposes up
    # to min(hot_slots, nblk) hot lanes. (A per-seq hot_slots // batch budget
    # would hide promoted blocks of an imbalanced batch from the read set —
    # breaking the promotion-rejoin invariant.)
    hot_rank = jnp.argsort(~resident, axis=1)[:, : min(pcfg.hot_slots, nblk)]
    hot_sel = jnp.take_along_axis(vidx, hot_rank, axis=1)
    hot_ok = jnp.take_along_axis(resident, hot_rank, axis=1)
    hot_ok &= ~(hot_rank[:, :, None] == win[:, None, :]).any(-1)
    read_idx = jnp.concatenate([win_idx, jnp.where(hot_ok, hot_sel, 0)], axis=1)
    read_valid = jnp.concatenate([win_valid, hot_ok], axis=1)
    read_block = jnp.concatenate(
        [jnp.where(win_valid, win, -1), jnp.where(hot_ok, hot_rank, -1)], axis=1
    ).astype(jnp.int32)
    return read_idx, read_valid, read_block


def rainbow_decode_step(
    cfg,
    pcfg: PagedConfig,
    params: Any,
    tokens: jax.Array,  # [B, 1]
    kv: RainbowKV,
    tp: int = 1,
    sc=None,
    mode: str = "full",
    scales: dict | None = None,  # int8 mode (pcfg.quantize): scale side pytree
    collect_mass: bool = False,  # also return this step's [B, nblk] block mass
    collect_slots: bool = False,  # also return the step's routed slots on held experts
):
    """One decode step for a dense-family LM, or a latent-attention MoE LM
    (DeepSeek-V3 block: `_mla_layers`), over the Rainbow paged cache.

    Its phases run under named scopes, so each op of the step names its
    phase in its op_name metadata: "translate" (pool indices and the sparse
    read set); per layer "qkv", "read" (the translated pool gather), "attend"
    and "mlp"; then "append", "observe" (the controller's block mass),
    "promote" (end of interval) and "logits". Where the rainbow_attention
    kernel reads the pools (full mode, float pools, a TPU and on-tile shapes:
    ops.backend decides), it runs as "attend/paged_attention" and there is
    no "read"; the layer scan then carries only the parameters and the
    layer index, and the kernel DMAs each live block of that layer from the
    stacked pools.

    Returns (logits, kv), then the int8 scales, the block mass and the
    routed slots on held experts (an int32 scalar, 0 without experts) where
    asked for.
    """
    if cfg.mla:
        if mode != "full" or pcfg.quantize:
            raise NotImplementedError(
                f"{cfg.name}: latent attention reads the paged cache in full mode "
                "over bfloat16 pools only (no sparse or int8 read of latent rows)")
    else:
        assert cfg.family in ("dense", "vlm"), "rainbow decode targets dense-family archs"
    b = tokens.shape[0]
    cur = kv.length
    x = L.embed_lookup(cfg, params["embed"], tokens)
    pos = jnp.full((b, 1), cur, jnp.int32)
    nblk = pcfg.blocks_per_seq

    seg = M.segments(cfg)[0]
    seg_params = params["segments"][seg.name]
    kernel = (mode == "full" and not pcfg.quantize and not cfg.mla
              and ra_ops.backend(pcfg.block_size, cfg.head_dim) != "ref")

    # Translation is layer-invariant: compute the virtual pool indices once.
    with jax.named_scope("translate"):
        resident, vidx = pool_indices(kv, pcfg, b)

        if mode == "sparse":
            read_idx, read_valid, read_block = sparse_read_set(
                kv, pcfg, b, precomputed=(resident, vidx)
            )
        else:
            read_idx = vidx
            read_valid = None

    def body(carry, xs):
        h = carry
        if kernel:
            pl, layer = xs
        elif pcfg.quantize:
            pl, cap_k_l, cap_v_l, hot_k_l, hot_v_l, csk, csv, hsk, hsv = xs
        else:
            pl, cap_k_l, cap_v_l, hot_k_l, hot_v_l = xs
        with jax.named_scope("qkv"):
            hn = L.apply_norm(cfg, pl["ln1"], h)
            q, k_new, v_new = attn.qkv_project(cfg, pl["attn"], hn, pos, use_rope=True)

        if kernel:
            with jax.named_scope("attend"):
                o, blk_mass = ra_ops.paged_decode_attention(
                    q[:, 0], k_new[:, 0], v_new[:, 0], kv.cap_k, kv.cap_v,
                    kv.hot_k, kv.hot_v, read_idx, layer, cur)
                o = o[:, None]
        else:
            with jax.named_scope("read"):
                pool_k = jnp.concatenate([cap_k_l, hot_k_l], axis=0)
                pool_v = jnp.concatenate([cap_v_l, hot_v_l], axis=0)
                kvs_, hd = pool_k.shape[-2], pool_k.shape[-1]
                if pcfg.quantize:
                    sk_pool = jnp.concatenate([csk, hsk], axis=0)
                    sv_pool = jnp.concatenate([csv, hsv], axis=0)
                    k_r = dequantize_kv(pool_k[read_idx], sk_pool[read_idx], x.dtype)
                    v_r = dequantize_kv(pool_v[read_idx], sv_pool[read_idx], x.dtype)
                    k_r = k_r.reshape(b, -1, kvs_, hd)
                    v_r = v_r.reshape(b, -1, kvs_, hd)
                else:
                    k_r = pool_k[read_idx].reshape(b, -1, kvs_, hd)
                    v_r = pool_v[read_idx].reshape(b, -1, kvs_, hd)
                k_r = jnp.concatenate([k_r, k_new], axis=1)  # fresh token attends itself
                v_r = jnp.concatenate([v_r, v_new], axis=1)

            smax = k_r.shape[1]
            with jax.named_scope("attend"):
                if mode == "sparse":
                    token_ok = jnp.repeat(read_valid, pcfg.block_size, axis=1)
                    valid = jnp.concatenate(
                        [token_ok, jnp.ones((b, 1), bool)], axis=1
                    )  # fresh token always readable
                    o, lane_mass = _attend_with_mass(
                        q, k_r, v_r, valid, pcfg.block_size, read_idx.shape[1]
                    )
                    # Scatter read-lane mass back to home blocks so the controller
                    # observes sparse reads too (lanes are deduplicated, so each
                    # block's mass lands exactly once; invalid lanes drop). Without
                    # this, sparse mode fed zero mass to observe_block_mass,
                    # nothing ever promoted, and a hot block leaving the trailing
                    # window was lost forever — the promotion-rejoin path existed
                    # only in full mode.
                    dest = jnp.where(read_block >= 0, read_block, nblk)
                    blk_mass = jnp.zeros((b, nblk), jnp.float32).at[
                        jnp.arange(b)[:, None], dest
                    ].add(lane_mass, mode="drop")
                else:
                    pos_ids = jnp.arange(smax)
                    valid = (pos_ids < cur) | (pos_ids == smax - 1)  # history + fresh
                    o, blk_mass = _attend_with_mass(
                        q, k_r, v_r, valid, pcfg.block_size, nblk
                    )

        with jax.named_scope("mlp"):
            h = h + attn.attn_output(pl["attn"], o)
            h2 = L.apply_norm(cfg, pl["ln2"], h)
            h = h + L.apply_mlp(cfg, pl["mlp"], h2, sc=sc)
        return h, (k_new[:, 0], v_new[:, 0], blk_mass)

    if cfg.mla:
        h, k_all, mass_all, slots = _mla_layers(cfg, params, x, pos, kv, read_idx, cur)
        v_all = None  # one latent pool
    else:
        if kernel:
            xs = (seg_params, jnp.arange(kv.cap_k.shape[0], dtype=jnp.int32))
        elif pcfg.quantize:
            xs = (seg_params, kv.cap_k, kv.cap_v, kv.hot_k, kv.hot_v,
                  scales["cap_k"], scales["cap_v"], scales["hot_k"], scales["hot_v"])
        else:
            xs = (seg_params, kv.cap_k, kv.cap_v, kv.hot_k, kv.hot_v)
        # "layers" names what the scan itself adds around the body's scopes:
        # the per-layer slices of the stacked pools (none on the kernel path)
        # and what XLA fuses into them
        with jax.named_scope("layers"):
            h, (k_all, v_all, mass_all) = jax.lax.scan(body, x, xs)

    with jax.named_scope("append"):
        if pcfg.quantize:
            kv, scales = append_token_q8(kv, pcfg, scales, k_all, v_all)
        else:
            kv = append_token(kv, pcfg, None, k_all, v_all)
    with jax.named_scope("observe"):
        step_mass = mass_all.sum(axis=0)  # [B, nblk] — the controller's access stream
        kv = observe_block_mass(kv, pcfg, step_mass)
    kv = dataclasses.replace(kv, length=kv.length + 1)

    with jax.named_scope("promote"):
        if pcfg.quantize:
            def do_promote(args):
                kv_, sc_ = args
                new, rep = end_interval_promote(kv_, pcfg)
                sc_ = promote_scales(sc_, pcfg, rep["plan"], rep["cand_sp"], rep["cand_pg"])
                return new, sc_

            kv, scales = jax.lax.cond(
                kv.step_in_interval >= pcfg.interval_steps, do_promote,
                lambda a: a, (kv, scales),
            )
        else:
            def do_promote(kv_):
                new, _ = end_interval_promote(kv_, pcfg)
                return new

            kv = jax.lax.cond(
                kv.step_in_interval >= pcfg.interval_steps, do_promote, lambda s: s, kv
            )

    with jax.named_scope("logits"):
        h = L.apply_norm(cfg, params["final_norm"], h)
        logits = L.lm_logits(cfg, params["embed"], h)
    out = (logits, kv) + ((scales,) if pcfg.quantize else ())
    if collect_mass:
        out = out + (step_mass,)
    if collect_slots:
        out = out + (slots if cfg.mla else jnp.zeros((), jnp.int32),)
    return out


def _mla_layers(cfg, params, x, pos, kv: RainbowKV, read_idx, cur):
    """The layers of a latent-attention (MLA) model, one scan per segment:
    the leading dense-MLP layers, then the MoE layers.

    Per layer: "qkv" (norm, projections, the latent row), "absorb" (W_UK
    into the query; W_UV and the output projection on the way out),
    "attend" (ops.paged_decode_attention in its latent mode: on a TPU the
    rainbow_attention kernel as "attend/latent_attention", else the jnp
    read), then "mlp" on a dense layer, or "route", "experts" and "shared"
    (models.moe.apply_moe_held) on a MoE layer. The scan carries the
    parameters and the layer index; the attention reads the stacked pools.

    Returns (h, latent rows [L, B, 1, W], block mass [L, B, nblk], routed
    slots on the held experts)."""

    def body(kind):
        def step(h, xs):
            pl, layer = xs
            with jax.named_scope("qkv"):
                hn = L.apply_norm(cfg, pl["ln1"], h)
                q_nope, q_rope, c, k_rope = mla.project(cfg, pl["attn"], hn, pos)
                row = mla.latent_row(cfg, c, k_rope)[:, 0, None]  # [B, 1, W]
            with jax.named_scope("absorb"):
                q = mla.absorb_query(cfg, pl["attn"], q_nope, q_rope)[:, 0]
            with jax.named_scope("attend"):
                o, blk_mass = ra_ops.paged_decode_attention(
                    q, row, None, kv.cap_k, None, kv.hot_k, None, read_idx, layer, cur,
                    scale=mla.softmax_scale(cfg), v_dim=cfg.mla_kv_rank)
            with jax.named_scope("absorb"):
                h = h + mla.absorb_output(cfg, pl["attn"], o[:, None])
            if kind == "moe":
                with jax.named_scope("route"):
                    h2 = L.apply_norm(cfg, pl["ln2"], h)
                routed, shared, slots = moe_mod.apply_moe_held(cfg, pl["moe"], h2)
                h = h + (routed + shared)
            else:
                with jax.named_scope("mlp"):
                    h2 = L.apply_norm(cfg, pl["ln2"], h)
                    h = h + L.apply_mlp(cfg, pl["mlp"], h2)
                slots = jnp.zeros((), jnp.int32)
            return h, (row, blk_mass, slots)

        return step

    rows, masses, slots = [], [], jnp.zeros((), jnp.int32)
    h = x
    for seg in M.segments(cfg):
        layers = jnp.arange(seg.start, seg.start + seg.length, dtype=jnp.int32)
        with jax.named_scope("layers"):
            h, (r, m, sl) = jax.lax.scan(
                body(seg.kind), h, (params["segments"][seg.name], layers))
        rows.append(r)
        masses.append(m)
        slots = slots + sl.sum()
    return h, jnp.concatenate(rows), jnp.concatenate(masses), slots


def record_mass_trace(
    cfg,
    pcfg: PagedConfig,
    params: Any,
    prompt: jax.Array,  # int32[B, P] prompt tokens (consumed prefill-by-decode)
    steps: int,  # total decode steps recorded (>= prompt length)
    tp: int = 1,
):
    """Run a real model decode and record the controller's access stream.

    Returns (MassTrace, final RainbowKV). The trace holds one [B, nblk]
    attention-mass row per decode step — exactly what observe_block_mass saw —
    so `engine.autotune` can replay the observe/promote control loop against
    it for any candidate ControlPolicy without re-running the model.
    """
    from repro.engine.autotune import MassTrace
    from repro.serving.steps import greedy_sample

    assert not pcfg.quantize, "mass-trace recording targets the fp pools"
    b, plen = prompt.shape
    if steps < plen:
        raise ValueError(f"steps ({steps}) must cover the prompt ({plen})")
    step = jax.jit(
        lambda p, t, k: rainbow_decode_step(cfg, pcfg, p, t, k, tp=tp,
                                            collect_mass=True)
    )
    kv = paged_init(cfg, pcfg, b, tp, cfg.num_layers)
    rows = []
    tok = prompt[:, :1]
    for t in range(steps):
        if t < plen:
            tok = prompt[:, t:t + 1]
        logits, kv, mass = step(params, tok, kv)
        rows.append(np.asarray(mass, np.float32))
        tok = greedy_sample(logits, cfg.vocab_size)
    trace = MassTrace(
        mass=np.stack(rows), block_size=pcfg.block_size, start_length=0
    )
    return trace, kv
