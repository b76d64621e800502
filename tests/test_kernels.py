"""Pallas kernels vs pure-jnp oracles: ONE parity matrix across backends.

The matrix is the ready gate for flipping the TPU default backend (ROADMAP):
every accelerated kernel with a ref oracle — the fused observe counter, the
two-stage counter, block_gather, and rainbow (paged decode) attention — is
checked through the same parametrized sweep of backend x dtype x odd shapes,
including the degenerate chunks the engine can legitimately produce
(zero-access intervals, single monitored row, single block, no valid
migration lanes). On CPU the kernel leg runs the Pallas interpreter; on a
real TPU the SAME matrix additionally runs compiled ("pallas"), so hardware
bring-up needs no new tests.

Integer kernels must match bit-for-bit (tol None); float kernels to
accumulation tolerance.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.block_gather.ops import migrate_blocks
from repro.kernels.flash_attention.ops import attention
from repro.kernels.page_counter.ops import count_accesses, observe_counts
from repro.kernels.rainbow_attention.ops import paged_decode_attention

PARITY_BACKENDS = (
    ("interpret", "pallas") if jax.default_backend() == "tpu"
    else ("interpret",)
)


# -- case builders: closure(force, rng) -> (ref_outs, kernel_outs, tol) ------


def _counting_inputs(rng, a, nsp, pages, n):
    sp = jnp.asarray(rng.integers(-1, nsp, a).astype(np.int32))
    pg = jnp.asarray(rng.integers(0, pages, a).astype(np.int32))
    wr = jnp.asarray(rng.random(a) < 0.3)
    mon = np.full(n, -1, np.int32)  # -1 holes: partially-filled monitor set
    mon[: max(n - 1, 1)] = rng.choice(nsp, max(n - 1, 1), replace=False)
    return sp, pg, wr, jnp.asarray(mon)


def _two_stage(a, nsp, pages, n):
    def run(force, rng):
        sp, pg, wr, mon = _counting_inputs(rng, a, nsp, pages, n)
        w = jnp.where(wr, 2, 1).astype(jnp.uint32)
        ref = count_accesses(sp, pg, w, mon, nsp, pages, force="ref")
        ker = count_accesses(sp, pg, w, mon, nsp, pages, force=force)
        return ref, ker, None

    return run


def _fused_observe(a, nsp, pages, n, write_weight):
    def run(force, rng):
        sp, pg, wr, mon = _counting_inputs(rng, a, nsp, pages, n)
        kw = dict(write_weight=write_weight)
        ref = observe_counts(sp, pg, wr, mon, nsp, pages, force="ref", **kw)
        ker = observe_counts(sp, pg, wr, mon, nsp, pages, force=force, **kw)
        return ref, ker, None

    return run


def _block_gather(nb, hot, k, dtype, all_invalid=False):
    def run(force, rng):
        cap = jax.random.normal(jax.random.PRNGKey(0), (nb, 4, 2, 8), dtype)
        hotp = jax.random.normal(jax.random.PRNGKey(1), (hot, 4, 2, 8), dtype)
        src = rng.integers(-1, nb, k).astype(np.int32)
        if all_invalid:
            src[:] = -1  # an interval that migrates nothing
        dst = np.resize(rng.choice(hot, min(k, hot), replace=False),
                        k).astype(np.int32)
        seen = set()  # valid lanes must target unique dst slots
        for i in range(k):
            if src[i] >= 0 and int(dst[i]) in seen:
                src[i] = -1
            elif src[i] >= 0:
                seen.add(int(dst[i]))
        src, dst = jnp.asarray(src), jnp.asarray(dst)
        ref = migrate_blocks(cap, hotp, src, dst, force="ref")
        ker = migrate_blocks(cap, hotp, src, dst, force=force)
        return (ref,), (ker,), None  # gather moves bits: exact in any dtype

    return run


def _paged_inputs(b, hp, kvs, hd, block, nblk, dtype, layers=2, seed=0):
    """Stacked pools, a fresh token and a translated block table in which
    about a third of the blocks are resident in distinct hot slots."""
    ks = jax.random.split(jax.random.PRNGKey(seed * 131 + b * 7 + hp), 7)
    ncap, nhot = b * nblk, b * nblk // 3 + 2
    q = jax.random.normal(ks[0], (b, hp, hd), dtype)
    k_new = jax.random.normal(ks[1], (b, kvs, hd), dtype)
    v_new = jax.random.normal(ks[2], (b, kvs, hd), dtype)
    pools = [jax.random.normal(k, (layers, n, block, kvs, hd), dtype)
             for k, n in zip(ks[3:], (ncap, ncap, nhot, nhot))]
    rng = np.random.default_rng(seed)
    resident = np.zeros(ncap, bool)
    resident[rng.choice(ncap, nhot - 2, replace=False)] = True
    slot = np.full(ncap, -1)
    slot[resident] = rng.permutation(nhot)[: nhot - 2]
    vidx = np.where(resident, ncap + slot, np.arange(ncap)).reshape(b, nblk)
    return q, k_new, v_new, pools, jnp.asarray(vidx, jnp.int32)


def _rainbow_attention(b, hp, kvs, hd, block, nblk, dtype):
    def run(force, rng):
        q, kn, vn, pools, vidx = _paged_inputs(b, hp, kvs, hd, block, nblk, dtype)
        length = jnp.int32(max(nblk * block - 2, 1))
        args = (q, kn, vn, *pools, vidx, jnp.int32(1), length)
        ref = paged_decode_attention(*args, force="ref")
        ker = paged_decode_attention(*args, force=force)
        return ref, ker, (2e-2 if dtype == jnp.bfloat16 else 2e-5)

    return run


def _dtype_tag(dtype):
    return "bf16" if dtype == jnp.bfloat16 else "f32"


PARITY_MATRIX = [
    # two-stage counter: baseline / odd lengths / single row / single sp
    pytest.param(_two_stage(100, 16, 8, 4), id="two_stage-100a"),
    pytest.param(_two_stage(517, 8, 32, 2), id="two_stage-517a"),
    pytest.param(_two_stage(1000, 32, 16, 8), id="two_stage-1000a"),
    pytest.param(_two_stage(0, 16, 8, 4), id="two_stage-zero_access"),
    pytest.param(_two_stage(129, 8, 8, 1), id="two_stage-single_row"),
    pytest.param(_two_stage(64, 1, 4, 1), id="two_stage-single_sp"),
    # fused observe counter (read/write split + write weighting)
    pytest.param(_fused_observe(300, 16, 8, 4, 3), id="fused_observe-300a"),
    pytest.param(_fused_observe(517, 8, 32, 2, 2), id="fused_observe-517a"),
    pytest.param(_fused_observe(0, 16, 8, 4, 2), id="fused_observe-zero_access"),
    pytest.param(_fused_observe(129, 8, 8, 1, 2), id="fused_observe-single_row"),
]
for dt in (jnp.float32, jnp.bfloat16):
    tag = _dtype_tag(dt)
    PARITY_MATRIX += [
        # block gather: baseline / overflow lanes / single lane / no lanes
        pytest.param(_block_gather(24, 6, 6, dt), id=f"block_gather-{tag}-24nb"),
        pytest.param(_block_gather(8, 3, 5, dt), id=f"block_gather-{tag}-8nb"),
        pytest.param(_block_gather(64, 16, 1, dt),
                     id=f"block_gather-{tag}-single_lane"),
        pytest.param(_block_gather(16, 4, 4, dt, all_invalid=True),
                     id=f"block_gather-{tag}-no_valid_lanes"),
        # rainbow paged decode attention: sweep + single-block edge
        pytest.param(_rainbow_attention(1, 4, 4, 16, 4, 3, dt),
                     id=f"rainbow_attn-{tag}-3blk"),
        pytest.param(_rainbow_attention(2, 8, 4, 32, 8, 6, dt),
                     id=f"rainbow_attn-{tag}-6blk"),
        pytest.param(_rainbow_attention(3, 8, 2, 64, 16, 4, dt),
                     id=f"rainbow_attn-{tag}-4blk"),
        pytest.param(_rainbow_attention(2, 4, 2, 32, 8, 1, dt),
                     id=f"rainbow_attn-{tag}-single_block"),
    ]


@pytest.mark.parametrize("backend", PARITY_BACKENDS)
@pytest.mark.parametrize("case", PARITY_MATRIX)
def test_kernel_parity_matrix(case, backend, rng):
    refs, kers, tol = case(backend, rng)
    for r, k in zip(refs, kers):
        if tol is None:  # float64 is exact for uint32 counts and bf16 blocks
            np.testing.assert_array_equal(
                np.asarray(k, np.float64), np.asarray(r, np.float64)
            )
        else:
            np.testing.assert_allclose(
                np.asarray(k, np.float32), np.asarray(r, np.float32),
                atol=tol, rtol=tol,
            )


# -- flash attention keeps its own sweep (no engine-facing ref-vs-default
#    dispatch to gate; tolerances are seq-length dependent) ------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,h,hd,causal", [
    (1, 128, 2, 32, True),
    (2, 256, 4, 64, True),
    (1, 256, 1, 128, False),
])
def test_flash_attention_sweep(b, s, h, hd, causal, dtype):
    key = jax.random.PRNGKey(s + hd)
    q = jax.random.normal(key, (b, s, h, hd), dtype)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, h, hd), dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, h, hd), dtype)
    ref = attention(q, k, v, causal=causal, force="ref")
    ker = attention(q, k, v, causal=causal, force="interpret")
    tol = 3e-2 if dtype == jnp.bfloat16 else 3e-5
    np.testing.assert_allclose(
        np.asarray(ker, np.float32), np.asarray(ref, np.float32), atol=tol, rtol=tol
    )


# -- rainbow paged decode attention: what it reads and what it reports -------


def _poison(pool, keep):
    """NaN in every block of the stacked pool except `keep` (layer, block)."""
    out = jnp.full_like(pool, jnp.nan)
    for lyr, blk in keep:
        out = out.at[lyr, blk].set(pool[lyr, blk])
    return out


def _read_case(name, dtype):
    b, hp, kvs, hd, block, nblk, layer = 2, 8, 4, 32, 8, 8, 1
    q, kn, vn, pools, vidx = _paged_inputs(b, hp, kvs, hd, block, nblk, dtype, seed=3)
    cap_k, cap_v, hot_k, hot_v = pools
    ncap = cap_k.shape[1]
    v = np.asarray(vidx)
    length = {"unread_blocks_nan": 11, "resident_reads_hot": nblk * block - 5,
              "length_zero": 0, "partial_last_block": 2 * block + 3,
              "mass_matches_step": 3 * block + 1}[name]
    live = v[:, : -(-length // block)].reshape(-1)
    if name == "unread_blocks_nan":
        # length far below capacity: only the live blocks' copies of this
        # layer that the table names are left readable
        cap = [(layer, i) for i in live if i < ncap]
        hot = [(layer, i - ncap) for i in live if i >= ncap]
        cap_k, cap_v = _poison(cap_k, cap), _poison(cap_v, cap)
        hot_k, hot_v = _poison(hot_k, hot), _poison(hot_v, hot)
    elif name == "resident_reads_hot":
        # a resident block's capacity copy is stale: the hot slot is read
        home = np.arange(ncap).reshape(b, nblk)[v >= ncap]
        cap_k = cap_k.at[layer, home].set(jnp.nan)
        cap_v = cap_v.at[layer, home].set(jnp.nan)
    elif name == "partial_last_block":
        # positions past the length inside the last live block hold garbage
        off = length % block
        for i in v[:, length // block]:
            if i >= ncap:
                hot_k = hot_k.at[layer, i - ncap, off:].set(jnp.nan)
                hot_v = hot_v.at[layer, i - ncap, off:].set(jnp.nan)
            else:
                cap_k = cap_k.at[layer, i, off:].set(jnp.nan)
                cap_v = cap_v.at[layer, i, off:].set(jnp.nan)
    args = (q, kn, vn, cap_k, cap_v, hot_k, hot_v, vidx, jnp.int32(layer),
            jnp.int32(length))
    return args, length, block


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=_dtype_tag)
@pytest.mark.parametrize("name", [
    "unread_blocks_nan", "resident_reads_hot", "length_zero",
    "partial_last_block", "mass_matches_step",
])
def test_rainbow_attention_read_set(name, dtype):
    args, length, block = _read_case(name, dtype)
    out, mass = paged_decode_attention(*args, force="interpret")
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    if name == "mass_matches_step":
        # the decode step's jnp path: gather through the concatenated pool,
        # append the fresh token, attend with per-block mass
        from repro.serving.rainbow_decode import _attend_with_mass

        q, kn, vn, cap_k, cap_v, hot_k, hot_v, vidx, layer, _ = args
        b, nblk = vidx.shape
        pool_k = jnp.concatenate([cap_k[layer], hot_k[layer]])[vidx]
        pool_v = jnp.concatenate([cap_v[layer], hot_v[layer]])[vidx]
        k_r = jnp.concatenate([pool_k.reshape(b, nblk * block, *kn.shape[1:]),
                               kn[:, None]], axis=1)
        v_r = jnp.concatenate([pool_v.reshape(b, nblk * block, *vn.shape[1:]),
                               vn[:, None]], axis=1)
        pos = jnp.arange(k_r.shape[1])
        ref_out, ref_mass = _attend_with_mass(
            q[:, None], k_r, v_r, (pos < length) | (pos == nblk * block), block, nblk)
        ref_out = ref_out[:, 0]
    else:
        ref_out, ref_mass = paged_decode_attention(*args, force="ref")
    assert bool(jnp.isfinite(out).all()) and bool(jnp.isfinite(mass).all())
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref_out, np.float32), atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(mass), np.asarray(ref_mass),
                               atol=1e-5, rtol=1e-5)
    # blocks past the length get exactly no mass
    assert not np.asarray(mass)[:, -(-length // block):].any()
