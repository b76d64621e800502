"""Two-stage access counter — Pallas TPU kernel (paper §III-B in hardware).

The memory-controller counting path as a tiled streaming kernel. The access
vectors are laid out as `(rows, lanes)` tiles and the grid walks them one
`(8, lanes)` block at a time, the (8, 128) tiling the TPU's vector memory
asks for. The counter tables are the kernel's outputs: their blocks never
move across the grid, so they stay in VMEM and are written back to HBM once.
They are small by design — that is the paper's point: O(mem/2MB) + N*1KB.

Scatter-adds inside a row are expressed as one-hot matmuls — the MXU-friendly
realization of "CAM + counter array" (TPU has no per-element atomic scatter;
a weight row times a [SP, lanes] one-hot IS the histogram). Each access row
is broadcast along sublanes against an iota column, so no in-kernel
transpose is needed. Counts accumulate in f32, which is exact below 2**24.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROWS = 8  # sublanes per access block


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _dot_nt(a, b):
    """a[M, L] . b[K, L]^T -> f32[M, K], exact for the integer counts here."""
    return jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def _kernel(mon_ref, sp_ref, page_ref, x_ref, s1_ref, *s2_refs, weights):
    """One (ROWS, lanes) access block into s1[1, NSP] and each s2[N, P].

    `weights(valid, x)` maps one row of the per-access operand to its
    stage-1 weights and one weight row per stage-2 table, all f32[1, lanes].
    """
    @pl.when(pl.program_id(0) == 0)
    def _init():
        s1_ref[...] = jnp.zeros_like(s1_ref)
        for ref in s2_refs:
            ref[...] = jnp.zeros_like(ref)

    lanes = sp_ref.shape[1]
    mon = mon_ref[...]  # int32[N, 1]
    sp_iota = jax.lax.broadcasted_iota(jnp.int32, (s1_ref.shape[1], lanes), 0)
    page_iota = jax.lax.broadcasted_iota(jnp.int32, (s2_refs[0].shape[1], lanes), 0)

    def row(r, carry):
        sp = sp_ref[pl.ds(r, 1), :]  # int32[1, lanes]
        page = page_ref[pl.ds(r, 1), :]
        w1, w2 = weights(sp >= 0, x_ref[pl.ds(r, 1), :])
        # stage 1: histogram over superpages
        sp_oh = (sp_iota == sp).astype(jnp.float32)  # [NSP, lanes]
        s1_ref[...] += _dot_nt(w1, sp_oh)
        # stage 2: monitored rows only
        hit = ((mon == sp) & (mon >= 0)).astype(jnp.float32)  # [N, lanes]
        page_oh = (page_iota == page).astype(jnp.float32)  # [P, lanes]
        for ref, w in zip(s2_refs, w2):
            ref[...] += _dot_nt(hit * w, page_oh)
        return carry

    jax.lax.fori_loop(0, ROWS, row, 0)


def _count(sp, page, x, monitored, num_superpages, pages_per_sp, weights,
           n_tables, lanes, interpret):
    """Tile the accesses, run the kernel, return uint32 (s1, *s2 tables)."""
    block = ROWS * lanes
    tiles = max(-(-sp.shape[0] // block), 1)
    pad = tiles * block - sp.shape[0]
    sp = jnp.pad(sp.astype(jnp.int32), (0, pad), constant_values=-1)
    page = jnp.pad(page.astype(jnp.int32), (0, pad))
    x = jnp.pad(x, (0, pad))
    n_mon = monitored.shape[0]
    n_pad = _round_up(n_mon, ROWS)
    nsp_pad = _round_up(num_superpages, 128)
    p_pad = _round_up(pages_per_sp, 128)
    mon = jnp.pad(monitored.astype(jnp.int32), (0, n_pad - n_mon),
                  constant_values=-1).reshape(n_pad, 1)

    access_spec = pl.BlockSpec((ROWS, lanes), lambda t: (t, 0))
    outs = pl.pallas_call(
        functools.partial(_kernel, weights=weights),
        grid=(tiles,),
        in_specs=[pl.BlockSpec((n_pad, 1), lambda t: (0, 0))] + [access_spec] * 3,
        out_specs=[pl.BlockSpec((1, nsp_pad), lambda t: (0, 0))]
        + [pl.BlockSpec((n_pad, p_pad), lambda t: (0, 0))] * n_tables,
        out_shape=[jax.ShapeDtypeStruct((1, nsp_pad), jnp.float32)]
        + [jax.ShapeDtypeStruct((n_pad, p_pad), jnp.float32)] * n_tables,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
    )(mon, *(v.reshape(tiles * ROWS, lanes) for v in (sp, page, x)))
    s1 = outs[0][0, :num_superpages]
    s2 = [o[:n_mon, :pages_per_sp] for o in outs[1:]]
    return tuple(t.astype(jnp.uint32) for t in (s1, *s2))


@functools.partial(
    jax.jit, static_argnames=("num_superpages", "pages_per_sp", "lanes", "interpret")
)
def two_stage_count(
    sp: jax.Array,  # int32[A] superpage per access (-1 = skip)
    page: jax.Array,  # int32[A]
    weight: jax.Array,  # uint32[A]
    monitored: jax.Array,  # int32[N] monitored superpage ids (-1 = unused row)
    num_superpages: int,
    pages_per_sp: int,
    *,
    lanes: int = 512,
    interpret: bool,
) -> tuple[jax.Array, jax.Array]:
    """Weighted histograms: (s1 u32[NSP], s2 u32[N, P])."""
    def weights(valid, w):
        w = jnp.where(valid, w, 0.0)
        return w, (w,)

    return _count(sp, page, weight.astype(jnp.float32), monitored,
                  num_superpages, pages_per_sp, weights, 1, lanes, interpret)


@functools.partial(
    jax.jit,
    static_argnames=(
        "num_superpages", "pages_per_sp", "write_weight", "lanes", "interpret",
    ),
)
def fused_observe_count(
    sp: jax.Array,  # int32[A] superpage per access (-1 = skip)
    page: jax.Array,  # int32[A]
    is_write: jax.Array,  # bool[A]
    monitored: jax.Array,  # int32[N] monitored superpage ids (-1 = unused row)
    num_superpages: int,
    pages_per_sp: int,
    write_weight: int = 2,
    *,
    lanes: int = 512,
    interpret: bool,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One-pass batch histograms: (s1 u32[NSP], s2_reads, s2_writes u32[N, P]).

    The counting step of engine.control's observe_tiers: stage 1 counts
    writes `write_weight` times heavier (§III-B); stage 2 keeps reads and
    writes in separate tables for the Eq. 1 utility split. Each access
    element is read once.
    """
    def weights(valid, wr):
        is_write = wr > 0
        w1 = jnp.where(valid, jnp.where(is_write, float(write_weight), 1.0), 0.0)
        w_r = jnp.where(valid & ~is_write, 1.0, 0.0)
        w_w = jnp.where(valid & is_write, 1.0, 0.0)
        return w1, (w_r, w_w)

    return _count(sp, page, is_write.astype(jnp.int32), monitored,
                  num_superpages, pages_per_sp, weights, 2, lanes, interpret)
