"""The DeepSeek-V3 block (Moonlight-16B-A3B) at a reduced size on the CPU:
the paged decode step against the benchmark's plain reference, the latent
mode of the rainbow_attention kernel against its jnp oracle, the expert
share against the uncut layer, the reference against transformers, and the
serving CLI."""
import dataclasses
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import load_module
from repro.configs import get_config, get_reduced_config
from repro.kernels.rainbow_attention.ops import paged_decode_attention
from repro.memory.kvcache import PagedConfig, paged_init
from repro.models import moe as moe_mod
from repro.serving.rainbow_decode import rainbow_decode_step

ROOT = pathlib.Path(__file__).resolve().parents[1]
REF = load_module(ROOT / "bench/reference/deepseek_v3.py", "deepseek_v3_reference")
DRIVER = load_module(ROOT / "bench/drivers/decode_mla_moe.py", "decode_mla_moe_driver")


def ref_cfg(c, held=None) -> dict:
    """The reference's configuration keys for a program ModelConfig."""
    return {"num_hidden_layers": c.num_layers, "first_k_dense_replace": c.moe_first_dense,
            "hidden_size": c.d_model, "num_attention_heads": c.num_heads,
            "kv_lora_rank": c.mla_kv_rank, "qk_nope_head_dim": c.mla_nope_dim,
            "qk_rope_head_dim": c.mla_rope_dim, "v_head_dim": c.mla_v_dim,
            "intermediate_size": c.d_ff, "moe_intermediate_size": c.moe_d_ff,
            "n_routed_experts": c.moe_num_experts, "n_routed_experts_held": held or c.moe_held,
            "num_experts_per_tok": c.moe_top_k, "n_shared_experts": c.moe_num_shared,
            "routed_scaling_factor": c.moe_routed_scale, "norm_topk_prob": c.moe_norm_topk,
            "vocab_size": c.vocab_size, "rms_norm_eps": c.norm_eps, "rope_theta": c.rope_theta}


def f32_setup(seed):
    """Reduced preset in float32, reference weights, the program's params."""
    cfg = dataclasses.replace(get_reduced_config("moonlight-16b-a3b"),
                              dtype="float32", param_dtype="float32")
    rc = ref_cfg(cfg)
    w = REF.make_weights(rc, seed, cfg.padded_vocab)
    params = jax.tree.map(lambda a: a.astype(jnp.float32), DRIVER.program_params(w, rc))
    return cfg, rc, w, params


def test_published_widths():
    c = get_config("moonlight-16b-a3b")
    assert (c.num_layers, c.d_model, c.num_heads, c.vocab_size) == (27, 2048, 16, 163840)
    assert (c.mla_kv_rank, c.mla_nope_dim, c.mla_rope_dim, c.mla_v_dim) == (512, 128, 64, 128)
    assert (c.moe_num_experts, c.moe_top_k, c.moe_num_shared, c.moe_d_ff) == (64, 6, 2, 1408)
    assert (c.d_ff, c.moe_first_dense, c.moe_scoring, c.moe_routed_scale) == (
        11264, 1, "sigmoid", 2.446)
    assert c.norm_eps == 1e-5 and c.rope_theta == 50000 and c.rope_interleave
    assert not c.tie_embeddings and c.latent_width == 640  # 512 + 64, padded to 128 lanes


@pytest.mark.parametrize("seed", [0, 1])
def test_paged_decode_matches_reference_forward(seed):
    """(a) Prefill-by-decode through the paged latent cache, promotions forced
    every two steps, against the reference's full forward logits. Both run in
    float32 (the program's matmuls accumulate in another order than the
    reference's `highest` einsums): 1e-5 on logits of scale ~0.7 is that
    noise (measured 2e-7); the reference computed with fp8 operands lies
    3e-2 off, and a bfloat16 program flips near-tied expert choices."""
    cfg, rc, w, params = f32_setup(seed)
    pcfg = PagedConfig(block_size=4, blocks_per_seq=8, hot_slots=4, top_n=2,
                       max_promotions=2, interval_steps=2)
    b, s = 2, 24
    toks = jax.random.randint(jax.random.PRNGKey(seed + 10), (b, s), 0, cfg.vocab_size)
    kv = paged_init(cfg, pcfg, b, 1, cfg.num_layers)
    assert kv.cap_v is None and kv.cap_k.shape[-2:] == (1, cfg.latent_width)
    step = jax.jit(lambda p, t, k: rainbow_decode_step(cfg, pcfg, p, t, k, collect_slots=True))
    out, slots = [], 0
    for t in range(s):
        logits, kv, n = step(params, toks[:, t:t + 1], kv)
        out.append(logits[:, 0, : cfg.vocab_size])
        slots += int(n)
    assert int((kv.remap.remap >= 0).sum()) > 0  # blocks were read from the hot pool
    # every routed slot lands on a held expert (the preset holds all of them)
    assert slots == b * s * cfg.moe_top_k * (cfg.num_layers - cfg.moe_first_dense)
    ref = REF.logits(rc, w, toks)
    err = float(jnp.abs(jnp.stack(out, 1) - ref).max())
    assert err < 1e-5, err
    ctrl = float(jnp.abs(REF.logits(rc, w, toks, REF.fp8) - ref).max())
    assert ctrl > 1e-5 * 100, ctrl


def test_sparse_and_int8_refuse_latent_attention():
    cfg, _, _, params = f32_setup(0)
    pcfg = PagedConfig(block_size=4, blocks_per_seq=8, hot_slots=4, top_n=2,
                       max_promotions=2, interval_steps=2)
    kv = paged_init(cfg, pcfg, 2, 1, cfg.num_layers)
    tok = jnp.zeros((2, 1), jnp.int32)
    with pytest.raises(NotImplementedError, match="latent"):
        rainbow_decode_step(cfg, pcfg, params, tok, kv, mode="sparse")
    with pytest.raises(NotImplementedError, match="latent"):
        paged_init(cfg, dataclasses.replace(pcfg, quantize=True), 2, 1, cfg.num_layers)


def _latent_inputs(seed=3, dtype=jnp.bfloat16):
    b, hp, w, block, nblk, layers, nhot = 2, 8, 128, 16, 6, 2, 4
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    ncap = b * nblk
    cap = jax.random.normal(ks[0], (layers, ncap, block, 1, w), jnp.float32).astype(dtype)
    hot = jax.random.normal(ks[1], (layers, nhot, block, 1, w), jnp.float32).astype(dtype)
    q = jax.random.normal(ks[2], (b, hp, w), jnp.float32).astype(dtype)
    row = jax.random.normal(ks[3], (b, 1, w), jnp.float32).astype(dtype)
    vidx = np.arange(ncap, dtype=np.int32).reshape(b, nblk)
    vidx[0, 1], vidx[1, 0], vidx[1, 3] = ncap + 2, ncap + 0, ncap + 3  # resident blocks
    return q, row, cap, hot, vidx, block, nblk


@pytest.mark.parametrize("case", ["unread_blocks_nan", "resident_reads_hot"])
@pytest.mark.parametrize("length", [0, 1, 17, 16 * 6 - 1, 16 * 6])
def test_latent_kernel_matches_oracle(length, case):
    """(b) The latent mode in interpret mode against the jnp oracle, lengths 0
    to capacity. Unread blocks, or the capacity copies of resident blocks,
    hold NaN: the kernel must read only live blocks, each from the pool the
    table names. Output within bf16 rounding of the oracle (2e-2: bf16
    probabilities and values); mass within 1e-5."""
    q, row, cap, hot, vidx, block, nblk = _latent_inputs()
    layer, ncap = 1, cap.shape[1]
    if case == "unread_blocks_nan":
        live = vidx[:, : -(-length // block)].reshape(-1)
        keep_cap = jnp.zeros(ncap, bool).at[jnp.asarray(live[live < ncap], jnp.int32)].set(True)
        keep_hot = jnp.zeros(hot.shape[1], bool).at[
            jnp.asarray(live[live >= ncap] - ncap, jnp.int32)].set(True)
        cap = cap.at[layer].set(jnp.where(keep_cap[:, None, None, None], cap[layer], jnp.nan))
        hot = hot.at[layer].set(jnp.where(keep_hot[:, None, None, None], hot[layer], jnp.nan))
        cap = cap.at[0].set(jnp.nan)  # another layer
    else:
        home = np.arange(ncap).reshape(vidx.shape)[vidx >= ncap]
        cap = cap.at[layer, home].set(jnp.nan)
    args = (q, row, None, cap, None, hot, None, jnp.asarray(vidx), jnp.int32(layer),
            jnp.int32(length))
    kw = {"scale": 0.09, "v_dim": 64}
    out, mass = paged_decode_attention(*args, force="interpret", **kw)
    if case == "unread_blocks_nan":
        # the oracle gathers every block: give it the clean pools
        _, _, cap_c, hot_c, _, _, _ = _latent_inputs()
        args = (q, row, None, cap_c, None, hot_c, None, jnp.asarray(vidx), jnp.int32(layer),
                jnp.int32(length))
    ref_out, ref_mass = paged_decode_attention(*args, force="ref", **kw)
    assert out.shape == (2, 8, 64)
    assert bool(jnp.isfinite(out).all()) and bool(jnp.isfinite(mass).all())
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref_out, np.float32),
                               atol=2e-2, rtol=2e-2)
    np.testing.assert_allclose(np.asarray(mass), np.asarray(ref_mass), atol=1e-5, rtol=1e-5)
    assert not np.asarray(mass)[:, -(-length // block):].any()


def test_expert_share_identity():
    """(c) The routed parts that four chips' shares of the experts compute,
    plus the shared experts counted once, equal the uncut reference layer
    (float32; 1e-7 is accumulation-order noise on outputs of scale ~5e-3)."""
    cfg = dataclasses.replace(get_reduced_config("moonlight-16b-a3b"),
                              dtype="float32", param_dtype="float32")
    rc = ref_cfg(cfg)
    w = REF.make_weights(rc, 5, cfg.padded_vocab)
    layer = {k: w[k][0] for k in REF.MOE_KEYS}
    h = jax.random.normal(jax.random.PRNGKey(6), (2, 5, cfg.d_model), jnp.float32)
    routed, shared = REF.moe_layer_parts(rc, layer, h)
    whole = routed + shared
    share = cfg.moe_num_experts // 4
    total, slots = jnp.zeros_like(h), 0
    for i in range(4):
        c = dataclasses.replace(cfg, moe_experts_held=share, moe_expert_offset=i * share)
        sl = slice(i * share, (i + 1) * share)
        p = {"router": layer["router"].astype(jnp.float32), "router_bias": layer["router_bias"],
             "wi": layer["e_up"][sl].astype(jnp.float32),
             "wg": layer["e_gate"][sl].astype(jnp.float32),
             "wo": layer["e_down"][sl].astype(jnp.float32),
             "shared": {"wi": layer["s_up"].astype(jnp.float32),
                        "wg": layer["s_gate"].astype(jnp.float32),
                        "wo": layer["s_down"].astype(jnp.float32)}}
        with jax.default_matmul_precision("highest"):
            r, s, n = moe_mod.apply_moe_held(c, p, h)
        np.testing.assert_allclose(np.asarray(s), np.asarray(shared), atol=1e-7, rtol=1e-5)
        ref_part, _ = REF.moe_layer_parts({**rc, "n_routed_experts_held": share},
                                          {**layer, **{k: layer[k][sl] for k in
                                                       ("e_gate", "e_up", "e_down")}},
                                          h, first=i * share)
        np.testing.assert_allclose(np.asarray(r), np.asarray(ref_part), atol=1e-7, rtol=1e-5)
        total, slots = total + r, slots + int(n)
    assert slots == h.shape[0] * h.shape[1] * cfg.moe_top_k  # every slot on exactly one share
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(whole),
                               atol=1e-7, rtol=1e-5)
    assert float(jnp.abs(routed).max()) > 1e-3  # the routed part is not vanishing


def test_reference_matches_transformers():
    """(d) The plain reference against transformers' DeepseekV3ForCausalLM
    built from a tiny DeepseekV3Config with the same weights, all in float32:
    logits within float32 noise (1e-5 on logits of scale ~0.7)."""
    torch = pytest.importorskip("torch")
    tf = pytest.importorskip("transformers")
    cfg = get_reduced_config("moonlight-16b-a3b")
    rc = ref_cfg(cfg)
    V = cfg.vocab_size
    w = jax.tree.map(lambda a: np.asarray(a, np.float32), REF.make_weights(rc, 7, V))
    hf_cfg = tf.DeepseekV3Config(
        vocab_size=V, hidden_size=cfg.d_model, intermediate_size=cfg.d_ff,
        moe_intermediate_size=cfg.moe_d_ff, num_hidden_layers=cfg.num_layers,
        num_attention_heads=cfg.num_heads, num_key_value_heads=cfg.num_heads,
        n_shared_experts=cfg.moe_num_shared, n_routed_experts=cfg.moe_num_experts,
        routed_scaling_factor=cfg.moe_routed_scale, kv_lora_rank=cfg.mla_kv_rank,
        q_lora_rank=None, qk_rope_head_dim=cfg.mla_rope_dim, v_head_dim=cfg.mla_v_dim,
        qk_nope_head_dim=cfg.mla_nope_dim, n_group=1, topk_group=1,
        num_experts_per_tok=cfg.moe_top_k, first_k_dense_replace=cfg.moe_first_dense,
        norm_topk_prob=True, rope_theta=cfg.rope_theta, rms_norm_eps=cfg.norm_eps,
        rope_interleave=True, attention_bias=False, tie_word_embeddings=False,
        max_position_embeddings=64, attn_implementation="eager")
    torch.manual_seed(0)
    model = tf.DeepseekV3ForCausalLM(hf_cfg).float().eval()
    t = lambda a: torch.from_numpy(np.array(a))  # noqa: E731  (a writable copy)
    d, H = cfg.d_model, cfg.num_heads
    sd = {"model.embed_tokens.weight": t(w["embed"]), "lm_head.weight": t(w["head"].T),
          "model.norm.weight": t(w["final_norm"])}
    nd = cfg.moe_first_dense
    for i in range(cfg.num_layers):
        pre = f"model.layers.{i}."
        sd[pre + "input_layernorm.weight"] = t(w["ln1"][i])
        sd[pre + "post_attention_layernorm.weight"] = t(w["ln2"][i])
        a = pre + "self_attn."
        sd[a + "q_proj.weight"] = t(w["wq"][i].reshape(d, -1).T)
        sd[a + "kv_a_proj_with_mqa.weight"] = t(w["wkv_a"][i].T)
        sd[a + "kv_a_layernorm.weight"] = t(w["kv_norm"][i])
        sd[a + "kv_b_proj.weight"] = t(w["wkv_b"][i].reshape(cfg.mla_kv_rank, -1).T)
        sd[a + "o_proj.weight"] = t(w["wo"][i].reshape(H * cfg.mla_v_dim, d).T)
        m = pre + "mlp."
        if i < nd:
            for k, name in (("w_gate", "gate_proj"), ("w_up", "up_proj"), ("w_down", "down_proj")):
                sd[m + f"{name}.weight"] = t(w[k][i].T)
            continue
        j = i - nd
        sd[m + "gate.weight"] = t(w["router"][j].T)
        sd[m + "gate.e_score_correction_bias"] = t(w["router_bias"][j])
        for e in range(cfg.moe_num_experts):
            for k, name in (("e_gate", "gate_proj"), ("e_up", "up_proj"), ("e_down", "down_proj")):
                sd[m + f"experts.{e}.{name}.weight"] = t(w[k][j, e].T)
        for k, name in (("s_gate", "gate_proj"), ("s_up", "up_proj"), ("s_down", "down_proj")):
            sd[m + f"shared_experts.{name}.weight"] = t(w[k][j].T)
    missing, unexpected = model.load_state_dict(sd, strict=False)
    assert not unexpected and not [k for k in missing if "rotary" not in k], (missing, unexpected)
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(8), (2, 20), 0, V))
    with torch.no_grad():
        want = model(torch.from_numpy(toks).long()).logits.numpy()
    got = np.asarray(REF.logits(rc, w, toks))
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    # the correction bias changes the choice somewhere, so the bias path is exercised
    assert np.abs(w["router_bias"]).max() > 0


def test_serve_cli_paged_reduced(monkeypatch, capsys):
    from repro.launch import serve

    monkeypatch.setattr(sys, "argv", ["serve", "--arch", "moonlight-16b-a3b", "--reduced",
                                      "--kv", "paged", "--batch", "2", "--prompt-len", "4",
                                      "--tokens", "4"])
    serve.main()
    out = capsys.readouterr().out
    assert "promoted hot blocks" in out and "decoded 4 tokens x 2 seqs" in out


def test_serve_cli_refuses_flat_latent(monkeypatch):
    from repro.launch import serve

    monkeypatch.setattr(sys, "argv", ["serve", "--arch", "moonlight-16b-a3b", "--reduced",
                                      "--kv", "flat"])
    with pytest.raises(SystemExit):
        serve.main()
