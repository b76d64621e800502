"""Model assembly for all 10 assigned architectures.

A model is a list of *segments* — homogeneous runs of layers executed with one
lax.scan each (keeps HLO compact for 36-48 layer configs):

  dense   : ln1 -> GQA attn -> +res ; ln2 -> MLP -> +res        (dense/vlm archs)
  moe     : ln1 -> GQA attn -> +res ; ln2 -> MoE -> +res
  ssm     : ln1 -> Mamba2 SSD -> +res                           (mamba2)
  hybrid  : ln1 -> [attn || ssm] avg -> +res ; ln2 -> MLP -> +res  (hymba)
  encdec  : ln1 -> self-attn -> +res ; ln2 -> cross-attn -> +res ; ln3 -> MLP
            (whisper decoder; the encoder is a separate stack of dense layers
             with bidirectional attention and sinusoidal positions)

deepseek-moe's leading dense-FFN layer forms its own 1-layer "dense" segment.
Per-layer sliding windows (hymba) ride through the scan as traced int32 flags.

Three entry points build the three step kinds: forward() (train/score),
prefill(), decode_step(). The flat KV cache lives here; the Rainbow paged cache
wraps decode in repro.memory/repro.serving.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.launch.axes import BATCH_AXES, MODEL_AXIS
from repro.models import attention as attn
from repro.models import layers as L
from repro.models import mla
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.config import ModelConfig

Params = dict[str, Any]

from repro.models.unroll_flag import set_scan_unroll, unroll as _unroll  # noqa: E402

# §Perf knob: shard the inter-layer residual stream over the model axis along
# the SEQUENCE dim (Megatron-style sequence parallelism). GSPMD then lowers the
# per-layer TP boundary collectives as reduce-scatter + all-gather instead of
# all-reduce — half the bytes on the wire and a smaller live residual.
_RESID_SEQ_PARALLEL = False


def set_resid_seq_parallel(value: bool) -> None:
    global _RESID_SEQ_PARALLEL
    _RESID_SEQ_PARALLEL = value


def _resid_spec():
    if _RESID_SEQ_PARALLEL:
        return (BATCH_AXES, MODEL_AXIS, None)
    return (BATCH_AXES, None, MODEL_AXIS)


# ---------------------------------------------------------------------------
# Segment plan
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SegSpec:
    name: str
    kind: str  # dense | moe | ssm | hybrid | encdec
    start: int
    length: int


def segments(cfg: ModelConfig) -> list[SegSpec]:
    lyr = cfg.num_layers
    if cfg.family == "moe":
        fd = cfg.moe_first_dense
        segs = []
        if fd:
            segs.append(SegSpec("dense0", "dense", 0, fd))
        segs.append(SegSpec("blocks", "moe", fd, lyr - fd))
        return segs
    if cfg.family == "ssm":
        return [SegSpec("blocks", "ssm", 0, lyr)]
    if cfg.family == "hybrid":
        return [SegSpec("blocks", "hybrid", 0, lyr)]
    if cfg.family == "audio":
        return [SegSpec("blocks", "encdec", 0, lyr)]
    return [SegSpec("blocks", "dense", 0, lyr)]  # dense, vlm


def seg_windows(cfg: ModelConfig, seg: SegSpec) -> np.ndarray:
    """Per-layer attention window (0 = unlimited) for a segment."""
    idx = np.arange(seg.start, seg.start + seg.length)
    if cfg.sliding_window and cfg.global_attn_every:
        w = np.where(idx % cfg.global_attn_every == 0, 0, cfg.sliding_window)
    elif cfg.sliding_window:
        w = np.full_like(idx, cfg.sliding_window)
    else:
        w = np.zeros_like(idx)
    return w.astype(np.int32)


# ---------------------------------------------------------------------------
# Init + specs
# ---------------------------------------------------------------------------


def _seg_init(cfg, key, tp, seg: SegSpec) -> Params:
    n = seg.length
    ks = jax.random.split(key, 8)
    p: Params = {"ln1": L.norm_init(cfg, cfg.d_model, n)}
    if cfg.mla:
        p["attn"] = mla.attn_init(cfg, ks[0], stacked=n)
    elif seg.kind in ("dense", "moe", "hybrid", "encdec"):
        p["attn"] = attn.attn_init(cfg, ks[0], tp, stacked=n)
    if seg.kind == "ssm" or seg.kind == "hybrid":
        p["ssm"] = ssm_mod.ssm_init(cfg, ks[1], tp, stacked=n)
    if seg.kind in ("dense", "hybrid", "encdec"):
        p["ln2"] = L.norm_init(cfg, cfg.d_model, n)
        p["mlp"] = L.mlp_init(cfg, ks[2], cfg.d_model, cfg.d_ff, stacked=n)
    if seg.kind == "moe":
        p["ln2"] = L.norm_init(cfg, cfg.d_model, n)
        p["moe"] = moe_mod.moe_init(cfg, ks[3], tp, stacked=n)
    if seg.kind == "encdec":
        p["xattn"] = attn.attn_init(cfg, ks[4], tp, stacked=n, cross=True)
        p["ln3"] = L.norm_init(cfg, cfg.d_model, n)
    return p


def _seg_specs(cfg, seg: SegSpec) -> Params:
    p: Params = {"ln1": L.norm_specs(cfg, stacked=True)}
    if cfg.mla:
        p["attn"] = mla.attn_specs(cfg, stacked=True)
    elif seg.kind in ("dense", "moe", "hybrid", "encdec"):
        p["attn"] = attn.attn_specs(cfg, stacked=True)
    if seg.kind in ("ssm", "hybrid"):
        p["ssm"] = ssm_mod.ssm_specs(cfg, stacked=True)
    if seg.kind in ("dense", "hybrid", "encdec"):
        p["ln2"] = L.norm_specs(cfg, stacked=True)
        p["mlp"] = L.mlp_specs(cfg, stacked=True)
    if seg.kind == "moe":
        p["ln2"] = L.norm_specs(cfg, stacked=True)
        p["moe"] = moe_mod.moe_specs(cfg, stacked=True)
    if seg.kind == "encdec":
        p["xattn"] = attn.attn_specs(cfg, stacked=True, cross=True)
        p["ln3"] = L.norm_specs(cfg, stacked=True)
    return p


def init_params(cfg: ModelConfig, key, tp: int = 1) -> Params:
    keys = jax.random.split(key, 4 + len(segments(cfg)))
    p: Params = {"embed": L.embed_init(cfg, keys[0])}
    p["segments"] = {
        seg.name: _seg_init(cfg, keys[2 + i], tp, seg)
        for i, seg in enumerate(segments(cfg))
    }
    p["final_norm"] = L.norm_init(cfg, cfg.d_model)
    if cfg.is_encoder_decoder:
        ne = cfg.num_encoder_layers
        enc_seg = SegSpec("enc", "dense", 0, ne)
        p["encoder"] = {
            "layers": _seg_init(cfg, keys[1], tp, enc_seg),
            "norm": L.norm_init(cfg, cfg.d_model),
        }
    return p


def param_specs(cfg: ModelConfig, tp: int = 1) -> Params:
    p: Params = {"embed": L.embed_specs(cfg)}
    p["segments"] = {seg.name: _seg_specs(cfg, seg) for seg in segments(cfg)}
    p["final_norm"] = L.norm_specs(cfg)
    if cfg.is_encoder_decoder:
        p["encoder"] = {
            "layers": _seg_specs(cfg, SegSpec("enc", "dense", 0, 1)),
            "norm": L.norm_specs(cfg),
        }
    return p


# ---------------------------------------------------------------------------
# Layer bodies (full-sequence: train / prefill)
# ---------------------------------------------------------------------------


def _sc(sc, x, *spec):
    return sc(x, P(*spec)) if sc is not None else x


def _attn_full_seq(
    cfg, pl, x, positions, window, *, causal, use_rope, tp, sc, impl, kv_out=False
):
    q, k, v = attn.qkv_project(cfg, pl, x, positions, use_rope=use_rope)
    q = _sc(sc, q, BATCH_AXES, None, MODEL_AXIS, None)
    k = _sc(sc, k, BATCH_AXES, None, MODEL_AXIS, None)
    v = _sc(sc, v, BATCH_AXES, None, MODEL_AXIS, None)
    if impl == "chunked":
        o = attn.attend_chunked(
            q, k, v, positions[0] if positions.ndim == 2 else positions,
            positions[0] if positions.ndim == 2 else positions, window, causal
        )
    else:
        qp = positions if positions.ndim == 2 else positions[None]
        mask = attn._causal_window_mask(qp, qp, window, causal)[:, None]  # [B|1,1,S,S]
        o = attn.attend_dense(q, k, v, mask)
    out = attn.attn_output(pl, o)
    if kv_out:
        return out, k, v
    return out, None, None


def _block_full_seq(cfg, kind, pl, x, positions, window, tp, sc, impl, enc_out=None):
    """One layer, full sequence. Returns (x', (k, v) or None, ssm_states or None)."""
    kv = None
    ssm_states = None
    h = L.apply_norm(cfg, pl["ln1"], x)
    if kind == "ssm":
        o, conv_st, ssm_st = ssm_mod.apply_ssm(cfg, pl["ssm"], h, tp, mode="train")
        x = x + o
        ssm_states = (conv_st, ssm_st)
    elif kind == "hybrid":
        ao, k, v = _attn_full_seq(
            cfg, pl["attn"], h, positions, window,
            causal=True, use_rope=True, tp=tp, sc=sc, impl=impl, kv_out=True,
        )
        so, conv_st, ssm_st = ssm_mod.apply_ssm(cfg, pl["ssm"], h, tp, mode="train")
        x = x + 0.5 * (ao + so)
        kv = (k, v)
        ssm_states = (conv_st, ssm_st)
        h2 = L.apply_norm(cfg, pl["ln2"], x)
        x = x + L.apply_mlp(cfg, pl["mlp"], h2, sc=sc)
    elif cfg.mla:
        qp = positions if positions.ndim == 2 else positions[None]
        mask = attn._causal_window_mask(qp, qp, window, True)[:, None]
        x = x + mla.attend_full_seq(cfg, pl["attn"], h, positions, mask)
        h2 = L.apply_norm(cfg, pl["ln2"], x)
        if kind == "moe":
            x = x + moe_mod.apply_moe(cfg, pl["moe"], h2, tp, sc=sc)
        else:
            x = x + L.apply_mlp(cfg, pl["mlp"], h2, sc=sc)
    else:
        causal = kind != "encoder"
        ao, k, v = _attn_full_seq(
            cfg, pl["attn"], h, positions, window,
            causal=causal, use_rope=causal, tp=tp, sc=sc, impl=impl, kv_out=True,
        )
        x = x + ao
        kv = (k, v)
        if kind == "encdec":
            hx = L.apply_norm(cfg, pl["ln2"], x)
            qx, _, _ = attn.qkv_project(cfg, pl["xattn"], hx, positions, use_rope=False)
            # cross k/v come from encoder output (precomputed per layer)
            ek, ev = enc_out
            o = attn.attend_dense(qx, ek, ev, None)
            x = x + attn.attn_output(pl["xattn"], o)
            h3 = L.apply_norm(cfg, pl["ln3"], x)
            x = x + L.apply_mlp(cfg, pl["mlp"], h3, sc=sc)
        elif kind == "moe":
            h2 = L.apply_norm(cfg, pl["ln2"], x)
            x = x + moe_mod.apply_moe(cfg, pl["moe"], h2, tp, sc=sc)
        else:  # dense / encoder
            h2 = L.apply_norm(cfg, pl["ln2"], x)
            x = x + L.apply_mlp(cfg, pl["mlp"], h2, sc=sc)
    x = _sc(sc, x, *_resid_spec())
    return x, kv, ssm_states


def _remat_wrap(fn, remat: str):
    if remat == "none":
        return fn
    policy = {
        "full": jax.checkpoint_policies.nothing_saveable,
        "dots": jax.checkpoint_policies.checkpoint_dots,
    }[remat]
    return jax.checkpoint(fn, policy=policy)


def _run_segment_full(
    cfg, seg: SegSpec, seg_params, x, positions, tp, sc, impl, remat,
    enc_kv=None, collect_cache=False,
):
    """Scan a segment over the full sequence. Returns (x, per-layer cache ys)."""
    windows = jnp.asarray(seg_windows(cfg, seg))

    def body(carry, xs):
        if enc_kv is not None:
            pl, w, ekv = xs
        else:
            pl, w = xs
            ekv = None
        x_new, kv, ssm_states = _block_full_seq(
            cfg, seg.kind, pl, carry, positions, w, tp, sc, impl, enc_out=ekv
        )
        ys = {}
        if collect_cache:
            if kv is not None:
                ys["k"], ys["v"] = kv
            if ssm_states is not None:
                ys["conv"], ys["ssm"] = ssm_states
        return x_new, ys

    body = _remat_wrap(body, remat)
    xs = (seg_params, windows) if enc_kv is None else (seg_params, windows, enc_kv)
    x, ys = jax.lax.scan(body, x, xs, unroll=_unroll(seg.length))
    return x, ys


# ---------------------------------------------------------------------------
# forward (train / score)
# ---------------------------------------------------------------------------


def _encode(cfg, params, frames, tp, sc, impl, remat):
    """Whisper encoder: frames [B,Se,D] (stub embeddings) + sinusoid positions."""
    b, se, d = frames.shape
    pos = jnp.arange(se)
    x = frames.astype(L.dtype_of(cfg)) + _sinusoid(se, d).astype(L.dtype_of(cfg))
    x = _sc(sc, x, BATCH_AXES, None, None)
    seg = SegSpec("enc", "encoder", 0, cfg.num_encoder_layers)
    ep = params["encoder"]["layers"]
    x, _ = _run_segment_full(cfg, seg, ep, x, pos, tp, sc, impl, remat)
    return L.apply_norm(cfg, params["encoder"]["norm"], x)


def _sinusoid(s: int, d: int) -> jax.Array:
    pos = jnp.arange(s, dtype=jnp.float32)[:, None]
    div = jnp.exp(jnp.arange(0, d, 2, dtype=jnp.float32) * (-np.log(10000.0) / d))
    pe = jnp.zeros((s, d), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(pos * div))
    pe = pe.at[:, 1::2].set(jnp.cos(pos * div))
    return pe


def _cross_kv_all_layers(cfg, params, enc_out, tp, sc):
    """Precompute cross-attention K/V for every decoder layer: [Lyr,B,Se,KVS,hd]."""
    seg_params = params["segments"]["blocks"]["xattn"]
    se = enc_out.shape[1]
    pos = jnp.arange(se)

    def per_layer(pl):
        _, k, v = attn.qkv_project(cfg, pl, enc_out, pos, use_rope=False)
        return k, v

    k, v = jax.vmap(per_layer)(seg_params)
    # vmap over stacked layer params maps q-projection too; recompute cheaply.
    return k, v


def forward(
    cfg: ModelConfig,
    params: Params,
    batch: dict[str, jax.Array],
    tp: int = 1,
    sc=None,
    attn_impl: str = "dense",
    remat: str = "none",
) -> jax.Array:
    """Full-sequence logits [B, S_dec, Vp] (train / scoring path)."""
    tokens = batch["tokens"]
    x = L.embed_lookup(cfg, params["embed"], tokens)
    if cfg.family == "vlm":
        ve = batch["vision_embeds"].astype(x.dtype)
        x = jnp.concatenate([ve, x], axis=1)
    x = _sc(sc, x, BATCH_AXES, None, None)
    positions = jnp.arange(x.shape[1])

    enc_kv = None
    if cfg.is_encoder_decoder:
        enc_out = _encode(cfg, params, batch["frames"], tp, sc, attn_impl, remat)
        ek, ev = _cross_kv_all_layers(cfg, params, enc_out, tp, sc)
        enc_kv = (ek, ev)

    for seg in segments(cfg):
        x, _ = _run_segment_full(
            cfg, seg, params["segments"][seg.name], x, positions, tp, sc,
            attn_impl, remat, enc_kv=enc_kv if seg.kind == "encdec" else None,
        )
    x = L.apply_norm(cfg, params["final_norm"], x)
    if cfg.family == "vlm":  # only text positions produce logits
        nv = batch["vision_embeds"].shape[1]
        x = x[:, nv:]
    logits = L.lm_logits(cfg, params["embed"], x)
    return logits


def loss_fn(cfg, params, batch, tp=1, sc=None, attn_impl="dense", remat="none"):
    logits = forward(cfg, params, batch, tp, sc, attn_impl, remat)
    mask = batch.get("loss_mask")
    if mask is None:
        mask = jnp.ones_like(batch["targets"], jnp.float32)
    return L.softmax_xent(logits, batch["targets"], mask)


# ---------------------------------------------------------------------------
# KV cache: init / prefill / decode
# ---------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, max_len: int, tp: int = 1) -> Params:
    if cfg.mla:
        raise NotImplementedError(
            f"{cfg.name}: latent attention decodes over the Rainbow-paged cache "
            "(repro.serving.rainbow_decode); the flat cache holds per-head K/V")
    cache: Params = {"len": jnp.zeros((), jnp.int32)}
    for seg in segments(cfg):
        c: Params = {}
        if seg.kind in ("dense", "moe", "hybrid", "encdec"):
            c.update(attn.cache_init(cfg, batch, max_len, tp, seg.length))
        if seg.kind in ("ssm", "hybrid"):
            c.update(ssm_mod.ssm_cache_init(cfg, batch, tp, seg.length))
        cache[f"seg:{seg.name}"] = c
    if cfg.is_encoder_decoder:
        enc_len = max_len  # cross cache sized by encoder frames at prefill
        cache["cross"] = attn.cache_init(cfg, batch, enc_len, tp, cfg.num_layers)
        cache["enc_len"] = jnp.zeros((), jnp.int32)
    return cache


def cache_specs(cfg: ModelConfig, seq_axis=None) -> Params:
    specs: Params = {"len": P()}
    for seg in segments(cfg):
        c: Params = {}
        if seg.kind in ("dense", "moe", "hybrid", "encdec"):
            c.update(attn.cache_specs(BATCH_AXES, seq_axis))
        if seg.kind in ("ssm", "hybrid"):
            c.update(ssm_mod.ssm_cache_specs(BATCH_AXES))
        specs[f"seg:{seg.name}"] = c
    if cfg.is_encoder_decoder:
        specs["cross"] = attn.cache_specs(BATCH_AXES, seq_axis)
        specs["enc_len"] = P()
    return specs


def prefill(
    cfg: ModelConfig,
    params: Params,
    batch: dict[str, jax.Array],
    cache: Params,
    tp: int = 1,
    sc=None,
    attn_impl: str = "dense",
) -> tuple[jax.Array, Params]:
    """Process the prompt; fill caches; return (last-position logits, cache)."""
    tokens = batch["tokens"]
    x = L.embed_lookup(cfg, params["embed"], tokens)
    if cfg.family == "vlm":
        ve = batch["vision_embeds"].astype(x.dtype)
        x = jnp.concatenate([ve, x], axis=1)
    x = _sc(sc, x, BATCH_AXES, None, None)
    s = x.shape[1]
    positions = jnp.arange(s)

    enc_kv = None
    if cfg.is_encoder_decoder:
        enc_out = _encode(cfg, params, batch["frames"], tp, sc, attn_impl, "none")
        ek, ev = _cross_kv_all_layers(cfg, params, enc_out, tp, sc)
        enc_kv = (ek, ev)
        cache = dict(cache)
        cross = dict(cache["cross"])
        se = ek.shape[2]
        cross["k"] = jax.lax.dynamic_update_slice(
            cross["k"], ek.astype(cross["k"].dtype), (0, 0, 0, 0, 0)
        )
        cross["v"] = jax.lax.dynamic_update_slice(
            cross["v"], ev.astype(cross["v"].dtype), (0, 0, 0, 0, 0)
        )
        cache["cross"] = cross
        cache["enc_len"] = jnp.asarray(se, jnp.int32)

    cache = dict(cache)
    for seg in segments(cfg):
        x, ys = _run_segment_full(
            cfg, seg, params["segments"][seg.name], x, positions, tp, sc,
            attn_impl, "none",
            enc_kv=enc_kv if seg.kind == "encdec" else None,
            collect_cache=True,
        )
        c = dict(cache[f"seg:{seg.name}"])
        if "k" in ys:  # write prompt K/V into the flat cache at offset 0
            c["k"] = jax.lax.dynamic_update_slice(
                c["k"], ys["k"].astype(c["k"].dtype), (0, 0, 0, 0, 0)
            )
            c["v"] = jax.lax.dynamic_update_slice(
                c["v"], ys["v"].astype(c["v"].dtype), (0, 0, 0, 0, 0)
            )
        if "ssm" in ys:
            c["conv"] = ys["conv"]
            c["ssm"] = ys["ssm"]
        cache[f"seg:{seg.name}"] = c
    cache["len"] = jnp.asarray(s, jnp.int32)

    x = L.apply_norm(cfg, params["final_norm"], x)
    logits = L.lm_logits(cfg, params["embed"], x[:, -1:])
    return logits, cache


def _block_decode(cfg, kind, pl, x, pos, window, c_slices, cur_len, tp, sc):
    """One layer, one token. c_slices holds this layer's cache leaves."""
    updates = {}
    h = L.apply_norm(cfg, pl["ln1"], x)
    if kind in ("dense", "moe", "hybrid", "encdec"):
        q, k, v = attn.qkv_project(cfg, pl["attn"], h, pos, use_rope=True)
        ck, cv = attn.cache_update(c_slices["k"], c_slices["v"], k, v, cur_len)
        updates["k"], updates["v"] = ck, cv
        ao = attn.decode_attend(q, ck, cv, cur_len + 1, window)
        ao = attn.attn_output(pl["attn"], ao)
    if kind in ("ssm", "hybrid"):
        so, conv_st, ssm_st = ssm_mod.apply_ssm(
            cfg, pl["ssm"], h, tp,
            conv_state=c_slices["conv"], ssm_state=c_slices["ssm"], mode="decode",
        )
        updates["conv"], updates["ssm"] = conv_st, ssm_st
    if kind == "ssm":
        x = x + so
    elif kind == "hybrid":
        x = x + 0.5 * (ao + so)
        h2 = L.apply_norm(cfg, pl["ln2"], x)
        x = x + L.apply_mlp(cfg, pl["mlp"], h2, sc=sc)
    elif kind == "encdec":
        x = x + ao
        hx = L.apply_norm(cfg, pl["ln2"], x)
        qx, _, _ = attn.qkv_project(cfg, pl["xattn"], hx, pos, use_rope=False)
        xo = attn.decode_attend(
            qx, c_slices["xk"], c_slices["xv"], c_slices["enc_len"], 0
        )
        x = x + attn.attn_output(pl["xattn"], xo)
        h3 = L.apply_norm(cfg, pl["ln3"], x)
        x = x + L.apply_mlp(cfg, pl["mlp"], h3, sc=sc)
    elif kind == "moe":
        x = x + ao
        h2 = L.apply_norm(cfg, pl["ln2"], x)
        x = x + moe_mod.apply_moe(cfg, pl["moe"], h2, tp, sc=sc)
    else:  # dense
        x = x + ao
        h2 = L.apply_norm(cfg, pl["ln2"], x)
        x = x + L.apply_mlp(cfg, pl["mlp"], h2, sc=sc)
    return x, updates


def decode_step(
    cfg: ModelConfig,
    params: Params,
    tokens: jax.Array,  # [B, 1]
    cache: Params,
    tp: int = 1,
    sc=None,
) -> tuple[jax.Array, Params]:
    """One decode step over all layers. Returns (logits [B,1,Vp], cache')."""
    cur = cache["len"]
    x = L.embed_lookup(cfg, params["embed"], tokens)
    x = _sc(sc, x, BATCH_AXES, None, None)
    pos = jnp.full((x.shape[0], 1), cur, jnp.int32)

    cache = dict(cache)
    for seg in segments(cfg):
        seg_cache = cache[f"seg:{seg.name}"]
        windows = jnp.asarray(seg_windows(cfg, seg))

        def body(carry, xs):
            pl, w, c_sl = xs
            if cfg.is_encoder_decoder:
                c_sl = dict(c_sl)
                c_sl["enc_len"] = cache["enc_len"]
            x_new, upd = _block_decode(
                cfg, seg.kind, pl, carry, pos, w, c_sl, cur, tp, sc
            )
            return x_new, upd

        xs_cache = dict(seg_cache)
        if cfg.is_encoder_decoder and seg.kind == "encdec":
            xs_cache["xk"] = cache["cross"]["k"]
            xs_cache["xv"] = cache["cross"]["v"]
        x, new_cache = jax.lax.scan(
            body, x, (params["segments"][seg.name], windows, xs_cache),
            unroll=_unroll(seg.length),
        )
        for k_ in ("xk", "xv"):
            new_cache.pop(k_, None)
        cache[f"seg:{seg.name}"] = {
            k_: v_ for k_, v_ in new_cache.items() if k_ in seg_cache
        }
    cache["len"] = cur + 1

    x = L.apply_norm(cfg, params["final_norm"], x)
    logits = L.lm_logits(cfg, params["embed"], x)
    return logits, cache
