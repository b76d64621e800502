"""Rainbow paged decode attention — Pallas TPU kernel.

The TPU-native form of the paper's split-TLB + bitmap + remap walk (Fig. 6):
the translated block table, the layer index and the current length arrive as
*scalar-prefetch* operands (SMEM — the TLB analogue). The stacked pools stay
in HBM (`pl.ANY`); for every live block the kernel DMAs ONE block of K and V
from the pool the table names for it — the hot pool slot of a resident block,
else the block's capacity-pool home — into VMEM. Nothing is concatenated and
no layer is sliced out of the stacked pools.

One program walks the batch. Per sequence, an in-kernel loop whose trip
count comes from SMEM visits chunks of `chunk` blocks, so the work grows
with the live length, not with the provisioned blocks; only blocks
0 .. (length-1)//block are read. The DMAs of the next chunk (of this or the
next sequence) run while the current chunk computes (double-buffered).

A chunk's K is viewed as rows [tokens*KVS, head_dim]: one 2-D dot
q[HP, hd] @ K^T gives every (query head, token, kv head) score, and pairs
whose kv head is not the query head's are masked away — grouped-query
attention without expanding K/V. bf16 operands, f32 accumulation and f32
softmax statistics; the probabilities are cast to the query dtype for PV.
Positions at or past the length are masked with `where` in the scores and
in V, so garbage there cannot leak (0 * NaN would).

Per sequence the kernel returns the running (m, l, acc) of the online
softmax over the history, and per query head and block the block's score
max and its exp-sum (blocks past the length: -inf-like max, sum 0). The
fresh token and the normalization are the caller's (ops.py). The per-block
statistics sit in lane-dense [HP, nblk] rows held in registers; a block's
entry is a select over its sequence's row (nblk / 128 vregs), the one cost
that grows with the provisioned blocks.

`latent_attention` is the same kernel in its latent mode (multi-head latent
attention): one pool per tier whose rows, one token each, are the keys of
every query head and whose first `v_dim` lanes are the values, so each
live block is one DMA; the softmax scale is the caller's. Its blocks are
narrower than a lane tile (16 rows), so a block's exp-sum is taken against
the running max and placed on its lane by one matmul (`_latent_chunk`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38
CHUNK_ROWS = 1024  # K rows (tokens x kv heads) per chunk


def _kernel(
    # scalar prefetch (SMEM)
    layer_ref,  # int32[1]
    length_ref,  # int32[1]  history length (positions already in the pools)
    vidx_ref,  # int32[B * nblk]  < ncap: capacity home; else ncap + hot slot
    # inputs
    q_ref,  # [B, HP, hd] (VMEM)
    gqa_ref,  # int32[HP, chunk*rows]: 1 where the row's kv head is the head's
    *refs,
    block: int,
    chunk: int,
    nblk: int,
    ncap: int,
    scale: float,
    v_dim: int,
):
    # GQA (v_dim 0):
    #   cap_k, cap_v   [L, ncap, rows, hd] (HBM)
    #   hot_k, hot_v   [L, nhot, rows, hd] (HBM)
    #   m_ref, l_ref   f32[B, HP, 1] (outputs, VMEM)
    #   acc_ref        f32[B, HP, hd]
    #   bm_ref, bl_ref f32[B, HP, nrow >= nblk]  per-block max and exp-sum
    #   kbuf, vbuf     [2, chunk, rows, hd] (scratch)
    #   sem            DMA semaphores [2 (k, v), 2 (slot)]
    # latent (v_dim > 0): one pool pair, cap_k and hot_k, whose rows are
    # keys and whose first v_dim lanes are values; acc_ref f32[B, HP, v_dim];
    # one buffer, kbuf, and semaphores [1, 2]
    if v_dim:
        cap_k, hot_k, m_ref, l_ref, acc_ref, bm_ref, bl_ref, kbuf, sem = refs
        pools = ((cap_k, hot_k, kbuf),)
    else:
        (cap_k, cap_v, hot_k, hot_v, m_ref, l_ref, acc_ref, bm_ref, bl_ref,
         kbuf, vbuf, sem) = refs
        pools = ((cap_k, hot_k, kbuf), (cap_v, hot_v, vbuf))
    nb, hp, hd = q_ref.shape
    rows = kbuf.shape[2]
    kvs = rows // block
    width = chunk * rows
    layer = layer_ref[0]
    length = length_ref[0]
    live = (length + block - 1) // block  # blocks holding history
    nch = (live + chunk - 1) // chunk  # chunks per sequence

    def blocks_in(c):  # live blocks in chunk c
        return jnp.minimum(chunk, live - c * chunk)

    def start(b, c, slot):
        def go(j, _):
            idx = vidx_ref[b * nblk + c * chunk + j]

            @pl.when(idx < ncap)
            def _():
                for i, (cap, _, buf) in enumerate(pools):
                    pltpu.make_async_copy(
                        cap.at[layer, idx], buf.at[slot, j], sem.at[i, slot]).start()

            @pl.when(idx >= ncap)
            def _():
                for i, (_, hot, buf) in enumerate(pools):
                    pltpu.make_async_copy(
                        hot.at[layer, idx - ncap], buf.at[slot, j], sem.at[i, slot]).start()

            return 0

        jax.lax.fori_loop(0, blocks_in(c), go, 0)

    def wait(c, slot):
        def go(j, _):
            # a wait needs only the destination and the semaphore: every
            # block copy has the same size, whichever pool it came from
            for i, (cap, _, buf) in enumerate(pools):
                pltpu.make_async_copy(cap.at[0, 0], buf.at[slot, j], sem.at[i, slot]).wait()
            return 0

        jax.lax.fori_loop(0, blocks_in(c), go, 0)

    gqa = gqa_ref[...] != 0  # [HP, width]
    col = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (width, 1), 0)
    nrow = bm_ref.shape[2]
    lane = jax.lax.broadcasted_iota(jnp.int32, (hp, nrow), 1)
    if v_dim:
        # latent rows are one token each (rows == block): row r of chunk c
        # is block c * chunk + r // block, the lane its exp-sum lands on
        to_lane = (jax.lax.broadcasted_iota(jnp.int32, (width, nrow), 1)
                   - jax.lax.broadcasted_iota(jnp.int32, (width, nrow), 0) // block)

    start(0, 0, 0)

    def seq_body(b, _):
        q = q_ref[b]  # [HP, hd]

        def chunk_body(c, carry):
            m_prev, l_prev, acc_prev, bm_row, bl_row = carry
            slot = jax.lax.rem(b * nch + c, 2)

            # prefetch the next chunk: this sequence's, else the next one's
            last = c + 1 == nch
            b_next = jnp.where(last, b + 1, b)

            @pl.when(b_next < nb)
            def _():
                start(b_next, jnp.where(last, 0, c + 1), 1 - slot)

            wait(c, slot)
            k = kbuf[slot].reshape(width, hd)
            v = k[:, :v_dim] if v_dim else vbuf[slot].reshape(width, hd)
            # rows of this chunk below the length: row // kvs < length - base
            nvalid = (length - c * chunk * block) * kvs
            ok = gqa & (col < nvalid)
            v = jnp.where(row < nvalid, v, jnp.zeros_like(v))
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
            s = jnp.where(ok, s * scale, NEG_INF)  # [HP, width]
            if v_dim:
                return _latent_chunk(s, ok, v, c, carry, to_lane, lane, chunk)

            # each block's max and exp-sum land in the sequence's lane-dense
            # [HP, nblk] rows, kept in registers until the sequence ends
            for j in range(chunk):
                sj = s[:, j * rows:(j + 1) * rows]
                mj = sj.max(axis=1, keepdims=True)
                ej = jnp.where(ok[:, j * rows:(j + 1) * rows], jnp.exp(sj - mj), 0.0)
                at = lane == c * chunk + j
                bm_row = jnp.where(at, mj, bm_row)
                bl_row = jnp.where(at, ej.sum(axis=1, keepdims=True), bl_row)

            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
            l_new = alpha * l_prev + p.sum(axis=1, keepdims=True)
            acc_new = alpha * acc_prev + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return m_new, l_new, acc_new, bm_row, bl_row

        init = (jnp.full((hp, 1), NEG_INF, jnp.float32),
                jnp.zeros((hp, 1), jnp.float32),
                jnp.zeros((hp, v_dim or hd), jnp.float32),
                jnp.full((hp, nrow), NEG_INF, jnp.float32),
                jnp.zeros((hp, nrow), jnp.float32))
        # with no history (nch == 0) the initial state is the answer
        m, l, acc, bm_row, bl_row = jax.lax.fori_loop(0, nch, chunk_body, init)
        m_ref[b], l_ref[b], acc_ref[b] = m, l, acc
        bm_ref[b], bl_ref[b] = bm_row, bl_row
        return 0

    jax.lax.fori_loop(0, nb, seq_body, 0)


def _latent_chunk(s, ok, v, c, carry, to_lane, lane, chunk):
    """One chunk of the latent mode's online softmax. A block's statistics
    are taken against the running max (its recorded max), and its exp-sum
    lands on its lane of the sequence's row by one matmul with the 0/1
    matrix `to_lane == c * chunk`: latent blocks are one row per token, 16
    to a block, so slicing the scores per block would cut lanes off the
    128-lane tile. Blocks past the length read that max and a sum of 0."""
    m_prev, l_prev, acc_prev, bm_row, bl_row = carry
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
    l_new = alpha * l_prev + p.sum(axis=1, keepdims=True)
    acc_new = alpha * acc_prev + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    place = (to_lane == c * chunk).astype(jnp.float32)  # [width, nrow]
    here = (lane >= c * chunk) & (lane < (c + 1) * chunk)
    bl_row = jnp.where(here, jax.lax.dot_general(
        p, place, (((1,), (0,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32), bl_row)
    bm_row = jnp.where(here, m_new, bm_row)
    return m_new, l_new, acc_new, bm_row, bl_row


@functools.partial(jax.jit, static_argnames=("interpret",))
def rainbow_attention(
    q: jax.Array,  # [B, HP, hd]
    cap_k: jax.Array,  # [L, ncap, block, KVS, hd]  stacked capacity pools
    cap_v: jax.Array,
    hot_k: jax.Array,  # [L, nhot, block, KVS, hd]  stacked hot pools
    hot_v: jax.Array,
    vidx: jax.Array,  # int32[B, nblk]  < ncap: home; else ncap + hot slot
    layer: jax.Array,  # int32 scalar
    length: jax.Array,  # int32 scalar: history positions per sequence
    *,
    interpret: bool,
):
    """(m, l, acc, block max, block exp-sum) of the history's attention:
    f32 [B, HP], [B, HP], [B, HP, hd], [B, HP, nblk], [B, HP, nblk]."""
    return _call(q, (cap_k, cap_v, hot_k, hot_v), vidx, layer, length,
                 scale=float(1.0 / np.sqrt(q.shape[-1])), v_dim=0, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("scale", "v_dim", "interpret"))
def latent_attention(
    q: jax.Array,  # [B, HP, W]  queries against latent rows
    cap: jax.Array,  # [L, ncap, block, 1, W]  stacked capacity latent pools
    hot: jax.Array,  # [L, nhot, block, 1, W]  stacked hot latent pools
    vidx: jax.Array,  # int32[B, nblk]
    layer: jax.Array,
    length: jax.Array,
    *,
    scale: float,
    v_dim: int,
    interpret: bool,
):
    """The latent mode (multi-head latent attention): one cached row per
    token, shared by every query head; keys are whole rows, values their
    first `v_dim` lanes, scores scaled by `scale`. Each live block is read
    once. Returns (m, l, acc [B, HP, v_dim], block max, block exp-sum)."""
    return _call(q, (cap, hot), vidx, layer, length, scale=scale, v_dim=v_dim,
                 interpret=interpret)


def _call(q, pools, vidx, layer, length, *, scale, v_dim, interpret):
    b, hp, hd = q.shape
    _, ncap, block, kvs, _ = pools[0].shape
    nblk = vidx.shape[1]
    rows = block * kvs
    chunk = max(1, min(nblk, CHUNK_ROWS // rows))  # blocks per chunk
    nrow = -(-nblk // 128) * 128  # lane-dense rows of per-block stats
    width = chunk * rows
    # rows are (token, kv head) pairs, kv head minor; query head h reads kv
    # head h // (HP // KVS)
    gqa = ((np.arange(width) % kvs)[None, :]
           == (np.arange(hp) // (hp // kvs))[:, None]).astype(np.int32)
    pools = [x.reshape(x.shape[0], x.shape[1], rows, hd) for x in pools]
    out_dim = v_dim or hd

    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    full = lambda shape: pl.BlockSpec(  # noqa: E731
        shape, lambda i, *_: (0,) * len(shape))
    stat = jax.ShapeDtypeStruct((b, hp, nrow), jnp.float32)
    npool = len(pools) // 2  # K and V, or one latent pool
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(1,),
        in_specs=[full((b, hp, hd)), full((hp, width))] + [any_spec] * len(pools),
        out_specs=[full((b, hp, 1)), full((b, hp, 1)), full((b, hp, out_dim)),
                   full(stat.shape), full(stat.shape)],
        scratch_shapes=[pltpu.VMEM((2, chunk, rows, hd), x.dtype) for x in pools[:npool]]
        + [pltpu.SemaphoreType.DMA((npool, 2))],
    )
    kernel = functools.partial(
        _kernel, block=block, chunk=chunk, nblk=nblk, ncap=ncap, scale=scale, v_dim=v_dim)
    m, l, acc, bm, bl = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, hp, 1), jnp.float32),
                   jax.ShapeDtypeStruct((b, hp, 1), jnp.float32),
                   jax.ShapeDtypeStruct((b, hp, out_dim), jnp.float32), stat, stat],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
    )(
        jnp.reshape(layer, (1,)).astype(jnp.int32),
        jnp.reshape(length, (1,)).astype(jnp.int32),
        vidx.reshape(-1).astype(jnp.int32),
        q, jnp.asarray(gqa), *pools,
    )
    return m[..., 0], l[..., 0], acc, bm[..., :nblk], bl[..., :nblk]
