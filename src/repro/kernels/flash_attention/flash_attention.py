"""Causal flash attention — Pallas TPU kernel (training substrate hot spot).

Standard tiling: grid (B, H, Q_blocks, KV_blocks); online softmax state (m, l,
acc) in VMEM scratch, persisted across the KV_block (innermost, "arbitrary")
grid dim; causal blocks above the diagonal are skipped via pl.when. Q/K/V tiles
are BlockSpec-mapped so each step holds (BQ + 2*BK) x hd in VMEM — sized for
~16 MB VMEM at hd<=256 with BQ=BK=128 (MXU-aligned).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -2.0e38


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *, bq, bk, nkv, causal):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if causal:
        run = ki * bk <= qi * bq + bq - 1  # skip blocks above the diagonal
    else:
        run = jnp.bool_(True)

    @pl.when(run)
    def _compute():
        q = q_ref[0, :, 0, :].astype(jnp.float32)  # [BQ, hd]
        k = k_ref[0, :, 0, :].astype(jnp.float32)  # [BK, hd]
        v = v_ref[0, :, 0, :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) / np.sqrt(q.shape[-1])
        if causal:
            qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev = m_ref[...][:, 0]
        l_prev = l_ref[...][:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_ref[...] = (l_prev * alpha + p.sum(axis=1))[:, None]
        m_ref[...] = m_new[:, None]
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(ki == nkv - 1)
    def _finish():
        o_ref[0, :, 0, :] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk", "interpret"))
def flash_attention(
    q: jax.Array,  # [B, S, H, hd]
    k: jax.Array,  # [B, S, H, hd] (kv pre-expanded)
    v: jax.Array,
    causal: bool = True,
    bq: int = 128,
    bk: int = 128,
    *,
    interpret: bool,
) -> jax.Array:
    b, s, h, hd = q.shape
    bq = min(bq, s)
    bk = min(bk, s)
    assert s % bq == 0 and s % bk == 0, "seq must divide block sizes"
    nq, nkv = s // bq, s // bk

    grid = (b, h, nq, nkv)
    qspec = pl.BlockSpec((1, bq, 1, hd), lambda bb, hh, qi, ki: (bb, qi, hh, 0))
    kspec = pl.BlockSpec((1, bk, 1, hd), lambda bb, hh, qi, ki: (bb, ki, hh, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, bq=bq, bk=bk, nkv=nkv, causal=causal),
        grid=grid,
        in_specs=[qspec, kspec, kspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
        ),
    )(q, k, v)
    return out
