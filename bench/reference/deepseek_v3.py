"""Plain reference of a DeepSeek-V3 block decoder (Moonlight-16B-A3B), and the
weights of a run.

The forward pass follows the published architecture (DeepseekV3ForCausalLM,
`q_lora_rank` null): token embedding; per layer RMSNorm, then multi-head
latent attention: q = h W_q split per head into a part without rotary
embedding (qk_nope_head_dim) and a rotary part (qk_rope_head_dim);
h W_kv_a split into the latent c (kv_lora_rank), RMS-normed by
kv_a_layernorm, and one rotary key shared by every head; c W_kv_b split per
head into keys without rotary embedding and values (v_head_dim). Rotary
embeddings (base rope_theta, no scaling) act on adjacent lane pairs
(rope_interleave: the lanes are de-interleaved to halves first). Causal
softmax attention scaled by (qk_nope_head_dim + qk_rope_head_dim)^-0.5,
output projection, residual; RMSNorm; the first `first_k_dense_replace`
layers a SwiGLU MLP, the rest a MoE: sigmoid scores of the router, the
top num_experts_per_tok chosen on the scores plus e_score_correction_bias
(n_group 1), weights the unbiased scores renormalized (norm_topk_prob) and
times routed_scaling_factor, routed SwiGLU experts of width
moe_intermediate_size plus shared experts of width n_shared_experts x that;
residual; final RMSNorm; logits from the untied head. RMSNorm eps is
rms_norm_eps, except kv_a_layernorm's: the published implementation builds
it without one, so it takes the norm's default, 1e-6.

It is float32 at `highest` matmul precision, the whole sequence at once,
one layer at a time, and imports nothing of the system under test.

The expert share: the weights hold the routed experts
0 .. n_routed_experts_held - 1 of each MoE layer; the router still scores
all n_routed_experts and picks its top-k among all of them, and an expert
not held contributes nothing (the chip's share of an expert-parallel
deployment; the program is given the same share).

`quant` fake-quantizes every matmul operand; the control passes an fp8
(e4m3) quantizer to compute the same model a precision below bfloat16, and
the witness `bf16` rounds the operands to the configuration's own precision.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def sizes(cfg: dict) -> dict:
    return {"L": cfg["num_hidden_layers"], "dense": cfg["first_k_dense_replace"],
            "d": cfg["hidden_size"], "H": cfg["num_attention_heads"],
            "r": cfg["kv_lora_rank"], "nope": cfg["qk_nope_head_dim"],
            "rope": cfg["qk_rope_head_dim"], "vd": cfg["v_head_dim"],
            "f": cfg["intermediate_size"], "fe": cfg["moe_intermediate_size"],
            "E": cfg["n_routed_experts"], "held": cfg["n_routed_experts_held"],
            "k": cfg["num_experts_per_tok"], "shared": cfg["n_shared_experts"],
            "scale": cfg["routed_scaling_factor"], "norm_topk": cfg["norm_topk_prob"],
            "V": cfg["vocab_size"], "eps": cfg["rms_norm_eps"], "theta": cfg["rope_theta"]}


BIAS_STD = 0.02  # e_score_correction_bias, drawn from the seed (see `assumed`)
KV_NORM_EPS = 1e-6  # kv_a_layernorm is built without rms_norm_eps: the norm's default


def make_weights(cfg: dict, seed: int, vocab_rows: int) -> dict:
    """All weights from the seed in one jitted call, in the served types:
    matrices (the router's too) bfloat16, norm scales and the correction
    bias float32. Attention keys are per layer (all layers); dense-MLP keys
    cover the first `first_k_dense_replace` layers, MoE keys the rest."""
    s = sizes(cfg)
    L, nd, d, H, r = s["L"], s["dense"], s["d"], s["H"], s["r"]
    nm = L - nd
    out_std = 0.02 / np.sqrt(2 * L)
    fs = s["shared"] * s["fe"]
    shapes = {
        "embed": ((vocab_rows, d), 0.02), "head": ((d, vocab_rows), 0.02),
        "wq": ((L, d, H, s["nope"] + s["rope"]), 0.02),
        "wkv_a": ((L, d, r + s["rope"]), 0.02),
        "wkv_b": ((L, r, H, s["nope"] + s["vd"]), 0.02),
        "wo": ((L, H, s["vd"], d), out_std),
        "w_gate": ((nd, d, s["f"]), 0.02), "w_up": ((nd, d, s["f"]), 0.02),
        "w_down": ((nd, s["f"], d), out_std),
        "router": ((nm, d, s["E"]), 0.02),
        "e_gate": ((nm, s["held"], d, s["fe"]), 0.02),
        "e_up": ((nm, s["held"], d, s["fe"]), 0.02),
        "e_down": ((nm, s["held"], s["fe"], d), out_std),
        "s_gate": ((nm, d, fs), 0.02), "s_up": ((nm, d, fs), 0.02),
        "s_down": ((nm, fs, d), out_std),
    }
    norms = {"ln1": (L, d), "ln2": (L, d), "kv_norm": (L, r), "final_norm": (d,)}

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(shapes) + len(norms) + 1)
        w = {}
        for k, (name, (shape, std)) in zip(keys, shapes.items()):
            w[name] = (jax.random.normal(k, shape, jnp.float32) * std).astype(jnp.bfloat16)
        for k, (name, shape) in zip(keys[len(shapes):], norms.items()):
            w[name] = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
        w["router_bias"] = BIAS_STD * jax.random.normal(keys[-1], (nm, s["E"]), jnp.float32)
        return w

    return make(jax.random.PRNGKey(seed % 2**31))


def _identity(x):
    return x


def fp8(x):
    """Per-tensor scaled float8_e4m3fn round trip, back to float32."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def bf16(x):
    """bfloat16 round trip, back to float32."""
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """Rotary embedding of adjacent lane pairs: de-interleave, then rotate
    halves (DeepSeek-V3's rope_interleave)."""
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def logits(cfg: dict, w: dict, tokens, quant=_identity):
    """f32 logits [B, S, vocab_size] of the causal forward pass over tokens."""
    return _forward(tuple(sorted(sizes(cfg).items())), quant)(w, jnp.asarray(tokens))


def _parts(size_items: tuple, quant):
    """(attention, dense MLP, MoE layer) of one layer, float32."""
    s = dict(size_items)
    H, nope, rope, eps = s["H"], s["nope"], s["rope"], s["eps"]
    f32 = lambda a: a.astype(jnp.float32)
    q_ = lambda eq, a, bb: jnp.einsum(eq, quant(a), quant(bb))

    def attention(x, p, pos, causal):
        h = _rms(x, p["ln1"], eps)
        q = q_("bsd,dhk->bshk", h, f32(p["wq"]))
        kv = q_("bsd,dk->bsk", h, f32(p["wkv_a"]))
        c = _rms(kv[..., :s["r"]], p["kv_norm"], KV_NORM_EPS)
        k_rope = _rope(kv[..., None, s["r"]:], pos, s["theta"])  # [B, S, 1, rope]
        q_rope = _rope(q[..., nope:], pos, s["theta"])
        kvb = q_("bsr,rhk->bshk", c, f32(p["wkv_b"]))
        k_nope, v = kvb[..., :nope], kvb[..., nope:]
        sc = (q_("bqhk,bshk->bhqs", q[..., :nope], k_nope)
              + q_("bqhk,bsk->bhqs", q_rope, k_rope[:, :, 0]))
        sc = sc / np.sqrt(nope + rope)
        a = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        o = q_("bhqs,bshk->bqhk", a, v)
        return x + q_("bqhk,hkd->bqd", o, f32(p["wo"]))

    def mlp(h, g, u, dn):
        return q_("bsf,fd->bsd", jax.nn.silu(q_("bsd,df->bsf", h, g))
                  * q_("bsd,df->bsf", h, u), dn)

    def dense(x, p):
        h = _rms(x, p["ln2"], eps)
        return x + mlp(h, f32(p["w_gate"]), f32(p["w_up"]), f32(p["w_down"]))

    def routed(h, p, first=0):
        """The held experts' part: experts first .. first + held - 1."""
        scores = jax.nn.sigmoid(q_("bsd,de->bse", h, f32(p["router"])))
        _, idx = jax.lax.top_k(scores + p["router_bias"], s["k"])
        wts = jnp.take_along_axis(scores, idx, axis=-1)
        if s["norm_topk"]:
            wts = wts / (wts.sum(-1, keepdims=True) + 1e-20)
        wts = wts * s["scale"]
        n = p["e_gate"].shape[0]
        mine = jax.nn.one_hot(idx - first, n, dtype=jnp.float32)  # ids not held: zeros
        per_expert = jnp.einsum("bsk,bske->bse", wts, mine)
        g = q_("bsd,edf->bsef", h, f32(p["e_gate"]))
        u = q_("bsd,edf->bsef", h, f32(p["e_up"]))
        y = q_("bsef,efd->bsed", jax.nn.silu(g) * u, f32(p["e_down"]))
        return jnp.einsum("bse,bsed->bsd", per_expert, y)

    def shared(h, p):
        return mlp(h, f32(p["s_gate"]), f32(p["s_up"]), f32(p["s_down"]))

    def moe(x, p):
        h = _rms(x, p["ln2"], eps)
        return x + routed(h, p) + shared(h, p)

    return attention, dense, moe, routed, shared


ATTN_KEYS = ("ln1", "ln2", "wq", "wkv_a", "kv_norm", "wkv_b", "wo")
DENSE_KEYS = ("w_gate", "w_up", "w_down")
MOE_KEYS = ("router", "router_bias", "e_gate", "e_up", "e_down", "s_gate", "s_up", "s_down")


@functools.lru_cache(maxsize=None)
def _forward(size_items: tuple, quant):
    s = dict(size_items)
    attention, dense, moe, _, _ = _parts(size_items, quant)
    nd = s["dense"]

    @jax.jit
    def run(w, tokens):
        pos = jnp.arange(tokens.shape[1])
        causal = pos[:, None] >= pos[None, :]
        with jax.default_matmul_precision("highest"):
            x = w["embed"][: s["V"]].astype(jnp.float32)[tokens]
            att = {k: w[k] for k in ATTN_KEYS}
            dense_p = {**{k: v[:nd] for k, v in att.items()}, **{k: w[k] for k in DENSE_KEYS}}
            moe_p = {**{k: v[nd:] for k, v in att.items()}, **{k: w[k] for k in MOE_KEYS}}
            x, _ = jax.lax.scan(
                lambda x, p: (dense(attention(x, p, pos, causal), p), None), x, dense_p)
            x, _ = jax.lax.scan(
                lambda x, p: (moe(attention(x, p, pos, causal), p), None), x, moe_p)
            x = _rms(x, w["final_norm"], s["eps"])
            return jnp.einsum("bsd,dv->bsv", x, w["head"][:, : s["V"]].astype(jnp.float32))

    return run


def moe_layer_parts(cfg: dict, p: dict, h, first: int = 0):
    """(routed part of experts first .., shared part) of one MoE layer's
    output for normed input h [B, S, d]; p holds that layer's MoE keys."""
    _, _, _, routed, shared = _parts(tuple(sorted(sizes(cfg).items())), _identity)
    with jax.default_matmul_precision("highest"):
        return routed(h, p, first), shared(h, p)


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
