"""Block-migration engine — Pallas TPU kernel (the paper's T_mig datapath).

Copies the selected hot blocks capacity->hot pool: grid over the migration plan;
src/dst indices are scalar-prefetched, and BOTH BlockSpec index_maps chase them,
so every grid step is one DMA capacity[src[k]] -> hot[dst[k]] with no compute.
On real hardware this overlaps decode compute (it touches disjoint buffers) —
the async-migration trick of §III-C.

Skip lanes (src < 0) are routed to a sink row appended to the hot pool (writes
land there and are sliced off), so no-op lanes can never race a real write to
slot 0. Untouched hot rows carry through via input/output aliasing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _kernel(src_ref, dst_ref, cap_ref, hot_in_ref, hot_out_ref):
    del hot_in_ref  # present only for the input/output alias
    hot_out_ref[...] = cap_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def block_gather(
    cap: jax.Array,  # [NB, block, KVS, hd]
    hot: jax.Array,  # [HOT, block, KVS, hd]
    src: jax.Array,  # int32[K] (-1 = skip lane)
    dst: jax.Array,  # int32[K]
    *,
    interpret: bool,
) -> jax.Array:
    kk = src.shape[0]
    nhot = hot.shape[0]
    block, kvs, hd = cap.shape[1], cap.shape[2], cap.shape[3]
    ok = src >= 0
    src_safe = jnp.where(ok, src, 0).astype(jnp.int32)
    dst_safe = jnp.where(ok, dst, nhot).astype(jnp.int32)  # -> sink row
    hot_padded = jnp.concatenate([hot, jnp.zeros_like(hot[:1])], axis=0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(kk,),
        in_specs=[
            pl.BlockSpec((1, block, kvs, hd), lambda k, s, d: (s[k], 0, 0, 0)),
            pl.BlockSpec((1, block, kvs, hd), lambda k, s, d: (d[k], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block, kvs, hd), lambda k, s, d: (d[k], 0, 0, 0)),
        scratch_shapes=[],
    )
    out = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(hot_padded.shape, hot.dtype),
        interpret=interpret,
        input_output_aliases={3: 0},  # hot_padded -> out (untouched rows keep)
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
    )(src_safe, dst_safe, cap, hot_padded)
    return out[:nhot]
