"""Plain reference of a Qwen3 dense decoder, and the weights of a run.

The forward pass follows the published architecture (Qwen3ForCausalLM):
token embedding; per layer RMSNorm, q/k/v projections, per-head RMSNorm of
q and k, rotary embeddings (half-split, base rope_theta), causal grouped-query
attention (query head h reads key/value head h // (heads / kv_heads)), output
projection, residual, RMSNorm, SwiGLU MLP, residual; final RMSNorm; logits
against the tied embedding. It is float32 at `highest` matmul precision,
one layer at a time, and imports nothing of the system under test.

`quant` fake-quantizes every matmul operand; the control passes an fp8
(e4m3) quantizer to compute the same model a precision below bfloat16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def sizes(cfg: dict) -> dict:
    return {"L": cfg["num_hidden_layers"], "d": cfg["hidden_size"],
            "H": cfg["num_attention_heads"], "KV": cfg["num_key_value_heads"],
            "hd": cfg["head_dim"], "f": cfg["intermediate_size"],
            "V": cfg["vocab_size"], "eps": cfg["rms_norm_eps"], "theta": cfg["rope_theta"]}


def make_weights(cfg: dict, seed: int, vocab_rows: int) -> dict:
    """All weights from the seed in one jitted call, in the served types:
    matrices bfloat16, norm scales float32."""
    s = sizes(cfg)
    L, d, H, KV, hd, f = s["L"], s["d"], s["H"], s["KV"], s["hd"], s["f"]
    out_std = 0.02 / np.sqrt(2 * L)
    shapes = {
        "embed": ((vocab_rows, d), 0.02), "wq": ((L, d, H, hd), 0.02),
        "wk": ((L, d, KV, hd), 0.02), "wv": ((L, d, KV, hd), 0.02),
        "wo": ((L, H, hd, d), out_std), "w_gate": ((L, d, f), 0.02),
        "w_up": ((L, d, f), 0.02), "w_down": ((L, f, d), out_std),
    }
    norms = {"ln1": (L, d), "ln2": (L, d), "q_norm": (L, hd), "k_norm": (L, hd),
             "final_norm": (d,)}

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(shapes) + len(norms))
        w = {}
        for k, (name, (shape, std)) in zip(keys, shapes.items()):
            w[name] = (jax.random.normal(k, shape, jnp.float32) * std).astype(jnp.bfloat16)
        for k, (name, shape) in zip(keys[len(shapes):], norms.items()):
            w[name] = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
        return w

    return make(jax.random.PRNGKey(seed % 2**31))


def _identity(x):
    return x


def fp8(x):
    """Per-tensor scaled float8_e4m3fn round trip, back to float32."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def logits(cfg: dict, w: dict, tokens, quant=_identity):
    """f32 logits [B, S, vocab_size] of the causal forward pass over tokens."""
    return _forward(tuple(sorted(sizes(cfg).items())), quant)(w, jnp.asarray(tokens))


@functools.lru_cache(maxsize=None)
def _forward(size_items: tuple, quant):
    s = dict(size_items)
    H, KV, hd, eps = s["H"], s["KV"], s["hd"], s["eps"]
    f32 = lambda a: a.astype(jnp.float32)
    q_ = lambda eq, a, bb: jnp.einsum(eq, quant(a), quant(bb))

    def layer(x, p, pos, causal):
        h = _rms(x, p["ln1"], eps)
        q = q_("bsd,dhk->bshk", h, f32(p["wq"]))
        k = q_("bsd,dhk->bshk", h, f32(p["wk"]))
        v = q_("bsd,dhk->bshk", h, f32(p["wv"]))
        q = _rope(_rms(q, p["q_norm"], eps), pos, s["theta"])
        k = _rope(_rms(k, p["k_norm"], eps), pos, s["theta"])
        k = jnp.repeat(k, H // KV, axis=2)
        v = jnp.repeat(v, H // KV, axis=2)
        sc = q_("bqhk,bshk->bhqs", q, k) / np.sqrt(hd)
        a = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        o = q_("bhqs,bshk->bqhk", a, v)
        x = x + q_("bqhk,hkd->bqd", o, f32(p["wo"]))
        h = _rms(x, p["ln2"], eps)
        g = q_("bsd,df->bsf", h, f32(p["w_gate"]))
        u = q_("bsd,df->bsf", h, f32(p["w_up"]))
        return x + q_("bsf,fd->bsd", jax.nn.silu(g) * u, f32(p["w_down"])), None

    @jax.jit
    def run(w, tokens):
        pos = jnp.arange(tokens.shape[1])
        causal = pos[:, None] >= pos[None, :]
        with jax.default_matmul_precision("highest"):
            emb = f32(w["embed"][: s["V"]])
            x = emb[tokens]
            per_layer = {k: w[k] for k in ("ln1", "ln2", "q_norm", "k_norm", "wq", "wk",
                                           "wv", "wo", "w_gate", "w_up", "w_down")}
            x, _ = jax.lax.scan(lambda x, p: layer(x, p, pos, causal), x, per_layer)
            x = _rms(x, w["final_norm"], eps)
            return q_("bsd,vd->bsv", x, emb)

    return run


def served_gaps(cfg: dict, w: dict, prompt, served, block: int = 4, quant=None):
    """For each served token, how far its reference logit lies below the
    reference's best at that position: float32 [B, new]. With `quant`, also
    the gaps of the tokens that the quantized model puts first."""
    prompt, served = np.asarray(prompt), np.asarray(served)
    p = prompt.shape[1]
    gaps, ctrl = [], []
    for i in range(0, prompt.shape[0], block):
        seq = jnp.asarray(np.concatenate([prompt[i:i + block], served[i:i + block]], 1))
        ref = logits(cfg, w, seq)[:, p - 1:-1]
        best = ref.max(-1)
        tok = jnp.asarray(served[i:i + block])
        gaps.append(np.asarray(best - jnp.take_along_axis(ref, tok[..., None], -1)[..., 0]))
        if quant is not None:
            low = logits(cfg, w, seq, quant)[:, p - 1:-1]
            pick = jnp.argmax(low, -1)
            ctrl.append(np.asarray(best - jnp.take_along_axis(ref, pick[..., None], -1)[..., 0]))
        del ref
    out = np.concatenate(gaps)
    return (out, np.concatenate(ctrl)) if quant is not None else out
