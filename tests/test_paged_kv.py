"""Rainbow paged KV cache (Layer B): exactness + promotion behaviour."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_reduced_config
from repro.core.remap import check_consistency
from repro.memory.kvcache import PagedConfig, end_interval_promote, paged_init
from repro.models import model as M
from repro.serving.rainbow_decode import rainbow_decode_step


def _setup(interval_steps=4, S=24):
    cfg = get_reduced_config("qwen3-4b")
    key = jax.random.PRNGKey(3)
    B = 2
    pcfg = PagedConfig(block_size=4, blocks_per_seq=S // 4, hot_slots=6, top_n=4,
                       max_promotions=4, interval_steps=interval_steps)
    params = M.init_params(cfg, key, tp=1)
    toks = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    return cfg, pcfg, params, toks, B, S


def test_rainbow_decode_exact_vs_flat():
    """THE invariant: tiered decode is numerically identical to flat decode,
    across promotions AND evictions (hot pool smaller than hot blocks)."""
    cfg, pcfg, params, toks, B, S = _setup()
    flat_step = jax.jit(lambda p, t, c: M.decode_step(cfg, p, t, c))
    rb_step = jax.jit(lambda p, t, k: rainbow_decode_step(cfg, pcfg, p, t, k))
    cache = M.init_cache(cfg, B, S, tp=1)
    kv = paged_init(cfg, pcfg, B, 1, cfg.num_layers)
    errs = []
    for t in range(S):
        tok = toks[:, t:t + 1]
        fl, cache = flat_step(params, tok, cache)
        rl, kv = rb_step(params, tok, kv)
        errs.append(float(jnp.abs(
            fl[..., :cfg.vocab_size] - rl[..., :cfg.vocab_size]).max()))
    assert max(errs) == 0.0, f"tiered decode diverged: {max(errs)}"
    assert int((kv.remap.remap >= 0).sum()) > 0, "no promotions happened"
    assert bool(check_consistency(kv.remap))


def test_rainbow_decode_kernel_path_matches_jnp(monkeypatch):
    """The rainbow_attention kernel (interpret mode) in the decode step against
    the jnp read, across promotions and evictions. Layer 0's K/V depend only on
    the token, so its pools match bitwise; deeper layers' K/V see the layer
    below through an attention output rounded to bf16 in another order, so
    they match to bf16 rounding, as do the logits and the step mass."""
    from repro.kernels.rainbow_attention import ops as ra_ops
    from repro.serving.rainbow_decode import record_mass_trace

    cfg, pcfg, params, toks, B, S = _setup()

    def run():
        step = jax.jit(lambda p, t, k: rainbow_decode_step(
            cfg, pcfg, p, t, k, collect_mass=True))
        kv = paged_init(cfg, pcfg, B, 1, cfg.num_layers)
        out = []
        for t in range(S):
            logits, kv, mass = step(params, toks[:, t:t + 1], kv)
            out.append((logits, kv, mass))
        return out, record_mass_trace(cfg, pcfg, params, toks, S)

    ref, (ref_trace, ref_kv) = run()
    monkeypatch.setattr(ra_ops, "backend", lambda *a, **k: "interpret")
    ker, (ker_trace, ker_kv) = run()

    def close(a, b, tol):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=tol, rtol=tol)

    for (rl, rkv, rm), (kl, kkv, km) in zip(ref, ker):
        close(kl, rl, 2e-2)
        close(km, rm, 2e-2)
        for name in ("cap_k", "cap_v", "hot_k", "hot_v"):
            r, k = getattr(rkv, name), getattr(kkv, name)
            np.testing.assert_array_equal(np.asarray(k[0]), np.asarray(r[0]))
            close(k, r, 2e-2)
        for r, k in zip(jax.tree.leaves((rkv.remap, rkv.dram, rkv.length)),
                        jax.tree.leaves((kkv.remap, kkv.dram, kkv.length))):
            np.testing.assert_array_equal(np.asarray(k), np.asarray(r))
    assert int((ker[-1][1].remap.remap >= 0).sum()) > 0, "no promotions happened"
    close(ker_trace.mass, ref_trace.mass, 2e-2)
    np.testing.assert_array_equal(np.asarray(ker_kv.remap.remap),
                                  np.asarray(ref_kv.remap.remap))


def test_promotion_respects_hot_pool_capacity():
    cfg, pcfg, params, toks, B, S = _setup(interval_steps=2)
    rb_step = jax.jit(lambda p, t, k: rainbow_decode_step(cfg, pcfg, p, t, k))
    kv = paged_init(cfg, pcfg, B, 1, cfg.num_layers)
    for t in range(S):
        _, kv = rb_step(params, toks[:, t:t + 1], kv)
        resident = int((kv.remap.remap >= 0).sum())
        assert resident <= pcfg.hot_slots
    assert int(kv.length) == S


def test_sparse_mode_runs_and_is_bounded():
    cfg, pcfg, params, toks, B, S = _setup()
    rb_full = jax.jit(lambda p, t, k: rainbow_decode_step(cfg, pcfg, p, t, k, mode="full"))
    rb_sparse = jax.jit(lambda p, t, k: rainbow_decode_step(cfg, pcfg, p, t, k, mode="sparse"))
    kv_f = paged_init(cfg, pcfg, B, 1, cfg.num_layers)
    kv_s = paged_init(cfg, pcfg, B, 1, cfg.num_layers)
    for t in range(S):
        lf, kv_f = rb_full(params, toks[:, t:t + 1], kv_f)
        ls, kv_s = rb_sparse(params, toks[:, t:t + 1], kv_s)
        assert bool(jnp.isfinite(ls).all())
    # sparse attends the trailing window; early-context divergence is allowed
    # but outputs must stay sane (same argmax for most steps is typical)


def _read_set_blocks(kv, pcfg, batch, seq):
    """Seq-local block ids currently in `seq`'s sparse read set."""
    from repro.serving.rainbow_decode import sparse_read_set

    _, valid, blocks = sparse_read_set(kv, pcfg, batch)
    v = np.asarray(valid[seq])
    return set(np.asarray(blocks[seq])[v].tolist())


def test_sparse_promotion_rejoin_crafted_mass():
    """THE rejoin invariant (satellite): a cold block outside the trailing
    window whose attention mass grows must be promoted at end_interval_promote
    and re-enter the sparse read set."""
    from repro.memory.kvcache import observe_block_mass

    # 12 blocks >> the 8-block trailing window, so old blocks fall out of
    # the sparse read set unless promotion brings them back
    cfg, pcfg, params, toks, B, S = _setup(S=48)
    nblk = pcfg.blocks_per_seq
    kv = paged_init(cfg, pcfg, B, 1, cfg.num_layers)
    kv = dataclasses.replace(kv, length=jnp.int32(S))  # all blocks valid

    target = 0  # block 0 is far behind the trailing window at length S
    assert target not in _read_set_blocks(kv, pcfg, B, seq=0)

    # interval 1: stage-1 sees seq 0's heat -> monitors rotate onto it
    hot = jnp.zeros((B, nblk), jnp.float32).at[0, target].set(4.0)
    kv = observe_block_mass(kv, pcfg, hot)
    kv, _ = end_interval_promote(kv, pcfg)
    # interval 2: stage-2 (now monitoring seq 0) sees the block's mass grow
    kv = observe_block_mass(kv, pcfg, hot)
    kv, rep = end_interval_promote(kv, pcfg)
    assert int(rep["promoted"]) >= 1

    rejoined = _read_set_blocks(kv, pcfg, B, seq=0)
    assert target in rejoined, (
        f"promoted block {target} must re-enter the sparse read set "
        f"(got {sorted(rejoined)})"
    )


def test_sparse_decode_promotes_and_rejoins_end_to_end():
    """Decode-driven rejoin: sparse mode must record real block mass (not
    zeros), promote hot history blocks, and read them once resident."""
    cfg, pcfg, params, toks, B, S = _setup(interval_steps=2)
    rb_sparse = jax.jit(
        lambda p, t, k: rainbow_decode_step(cfg, pcfg, p, t, k, mode="sparse"))
    kv = paged_init(cfg, pcfg, B, 1, cfg.num_layers)
    for t in range(S):
        _, kv = rb_sparse(params, toks[:, t:t + 1], kv)
    resident = int((kv.remap.remap >= 0).sum())
    assert resident > 0, "sparse decode never promoted a block"
    # every resident block is part of the sparse read set again
    for seq in range(B):
        in_set = _read_set_blocks(kv, pcfg, B, seq)
        rm = np.asarray(kv.remap.remap[seq])
        for blk in np.nonzero(rm >= 0)[0].tolist():
            assert blk in in_set


def test_interval_promote_copies_payload():
    cfg, pcfg, params, toks, B, S = _setup()
    kv = paged_init(cfg, pcfg, B, 1, cfg.num_layers)
    # fabricate stage-2 heat on (seq 0, block 1)
    s2c = kv.s2.counts
    kv = dataclasses.replace(
        kv,
        s2=dataclasses.replace(kv.s2, psn=jnp.array([0, 1, -1, -1], jnp.int32),
                               counts=s2c.at[0, 1].set(jnp.uint16(2000))),
        cap_k=kv.cap_k.at[:, 1].set(1.25),  # block 1 of seq 0
        length=jnp.int32(S),
    )
    kv2, rep = end_interval_promote(kv, pcfg)
    assert int(rep["promoted"]) >= 1
    in_fast, slot = jax.jit(
        lambda r: __import__("repro.core.remap", fromlist=["translate"]).translate(
            r, jnp.array([0], jnp.int32), jnp.array([1], jnp.int32))
    )(kv2.remap)
    assert bool(in_fast[0])
    s = int(slot[0])
    np.testing.assert_allclose(np.asarray(kv2.hot_k[:, s], np.float32), 1.25)


def test_int8_quantized_paged_decode_close():
    """Beyond-paper A3: int8 pools + per-token scales track flat decode."""
    import jax

    from repro.memory.kvcache import paged_scales_init

    cfg, pcfg0, params, toks, B, S = _setup()
    pcfg = dataclasses.replace(pcfg0) if False else PagedConfig(
        block_size=4, blocks_per_seq=S // 4, hot_slots=6, top_n=4,
        max_promotions=4, interval_steps=4, quantize=True)
    flat_step = jax.jit(lambda p, t, c: M.decode_step(cfg, p, t, c))
    q8_step = jax.jit(
        lambda p, t, k, s: rainbow_decode_step(cfg, pcfg, p, t, k, scales=s))
    cache = M.init_cache(cfg, B, S, tp=1)
    kv = paged_init(cfg, pcfg, B, 1, cfg.num_layers)
    sc = paged_scales_init(pcfg, B, cfg.kv_store(1), cfg.num_layers)
    agree = 0
    for t in range(S):
        tok = toks[:, t:t + 1]
        fl, cache = flat_step(params, tok, cache)
        rl, kv, sc = q8_step(params, tok, kv, sc)
        v = cfg.vocab_size
        err = float(jnp.abs(fl[..., :v] - rl[..., :v]).max())
        assert err < 0.1, f"int8 decode drifted: {err}"
        agree += int((jnp.argmax(fl[..., :v], -1) == jnp.argmax(rl[..., :v], -1)).all())
    assert agree >= S - 4  # near-perfect greedy agreement
    assert kv.cap_k.dtype == jnp.int8
