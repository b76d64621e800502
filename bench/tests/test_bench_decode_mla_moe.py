"""The latent-attention MoE decode cell's driver at a size a CPU holds: a
sound run is correct and reads its counters, the timed path broken
underneath is not, and neither is the control (the reference in fp8, a
precision below the bfloat16 the configuration states)."""
import dataclasses
import pathlib
import time

import jax
import jax.numpy as jnp
import pytest

from bench import flops_mla_moe, harness

ROOT = pathlib.Path(__file__).resolve().parents[2]
CELL = "decode-moonlight-16b-a3b-paged"
SMALL = {
    "config": {"num_hidden_layers": 4, "hidden_size": 256, "intermediate_size": 512,
               "num_attention_heads": 4, "num_key_value_heads": 4, "kv_lora_rank": 64,
               "qk_nope_head_dim": 32, "qk_rope_head_dim": 32, "v_head_dim": 32,
               "moe_intermediate_size": 64, "n_routed_experts": 16, "n_routed_experts_held": 8,
               "num_experts_per_tok": 4, "n_shared_experts": 1, "vocab_size": 4096},
    "traffic": {"batch": 4, "prompt_len": 16, "new_tokens": 32, "blocks_per_seq": 16,
                "block_size": 4, "check_sequences": 4},
}


def run(traced=False, seed=2**31 + 7):
    jax.clear_caches()  # a patched program must be traced again
    return harness.run_cell(ROOT, CELL, seed, 0.1, traced, t_start=time.perf_counter(),
                            require_chip=False, overrides=SMALL, log=lambda *a, **k: None)


@pytest.mark.parametrize("traced", [False, True])
def test_sound_run_is_correct(traced):
    line = run(traced)
    assert line["correct"], line["checks"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    if traced:
        # the rooflines need the chip's peaks (and the kernel, a TPU); the
        # idle share and the per-call compile time read on the CPU
        assert set(line["metrics"]) == {"mla_moe_decode.device_idle_share",
                                        "decode.compile_s_per_call"}
        assert line["device"]["busy_s"] > 0 and "breakdown" in line
    else:
        assert set(line["metrics"]) == {"decode_tokens_per_s", "setup_s"}


def test_counters_count_held_expert_slots():
    bench = harness.Bench(ROOT)
    c = bench.cell(CELL)
    cfg = {**bench.config(c["config"]), **SMALL["config"]}
    mix = {**bench.traffic(c["traffic"]), **SMALL["traffic"]}
    drv = bench.driver(mix["driver"]).Driver(cfg, mix, 11, jax.devices(),
                                             bench.reference(cfg["reference"]))
    drv.unit()
    drv.unit()
    ctr = drv.counters()
    steps = mix["prompt_len"] + mix["new_tokens"] - 1
    routed = 2 * mix["batch"] * steps * cfg["num_experts_per_tok"] * (
        cfg["num_hidden_layers"] - cfg["first_k_dense_replace"])
    # half the experts are held: about half the routed slots land on them
    assert ctr["calls"] == 2 and 0.25 * routed < ctr["local_expert_slots"] < 0.75 * routed


def _state_unchanged(monkeypatch):
    from repro.serving import rainbow_decode

    monkeypatch.setattr(rainbow_decode, "append_token", lambda kv, *a, **k: kv)


def _token_altered(monkeypatch):
    from repro.launch import serve

    monkeypatch.setattr(serve, "greedy_sample", lambda logits, v: (
        jnp.argmax(logits[..., :v], axis=-1).astype(jnp.int32) + 1) % v)


def _half_batch(monkeypatch):
    """Only the first half of the batch is decoded; its tokens fill the rest."""
    from repro.launch import serve

    real = serve.generate

    def half(cfg, params, prompt, new_tokens, pcfg=None, **kw):
        gen = real(cfg, params, prompt[: prompt.shape[0] // 2], new_tokens, pcfg, **kw)
        return dataclasses.replace(gen, tokens=jnp.concatenate([gen.tokens, gen.tokens]))

    monkeypatch.setattr(serve, "generate", half)


@pytest.mark.parametrize("fault", [_state_unchanged, _token_altered, _half_batch])
def test_broken_timed_path_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    try:
        line = run()
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert not line["correct"], line["checks"]
    assert line["failed"] >= 1


def test_flops_by_hand():
    """(f) bench/flops_mla_moe.py against a count by hand of a tiny step."""
    cfg = {"num_hidden_layers": 3, "first_k_dense_replace": 1, "hidden_size": 4,
           "num_attention_heads": 2, "kv_lora_rank": 3, "qk_nope_head_dim": 2,
           "qk_rope_head_dim": 1, "v_head_dim": 2, "intermediate_size": 5,
           "moe_intermediate_size": 2, "n_routed_experts": 6, "n_routed_experts_held": 2,
           "n_shared_experts": 1, "vocab_size": 7}
    # per layer attention matmuls: q 4*2*3=24, kv_a 4*4=16, W_UK 2*2*3=12,
    # W_UV 2*3*2=12, o 2*2*4=16 -> 80; dense MLP 3*4*5=60; per MoE layer
    # router 4*6=24, shared 3*4*2=24; head 4*7=28
    matmul = 3 * 80 + 60 + 2 * 48 + 28  # 424
    batch, length = 2, 5
    absorbed = 2 * 2 * (3 + 1 + 3) * (length + 1) * batch * 3  # 1008
    ops, least = flops_mla_moe.decode_step(cfg, batch, length)
    assert ops == 2 * matmul * batch + absorbed
    # bytes: attention weights per layer q 24, kv_a 16, kv_b 3*2*4=24, o 16
    # -> 80 (x3); dense 60; MoE router 24 + held and shared experts
    # 3*4*2*(2+1)=72 (x2); head 28 -> 520 bf16 matrices; norms 3*(4+4+3)+4
    # and biases 2*6 -> 49 float32; embedding rows 2*4 bf16; latent rows
    # (3+1)*3 per position: 5 positions read and 1 written per sequence;
    # logits 2*7 float32
    assert least == 2 * 520 + 4 * 49 + 2 * 8 + 2 * 12 * batch * length + 2 * 12 * batch + 4 * 14
    assert flops_mla_moe.expert_ops(cfg, 10) == 10 * 3 * 4 * 2 * 2
    assert flops_mla_moe.latent_read(cfg, batch, length) == 2 * 3 * 4 * length * batch


# wide and deep enough that the control's roundings move tokens as they do at
# the cell's own size (there: program 7-10%, control 49-54% off the best)
CONTROL = {
    "config": {"num_hidden_layers": 4, "hidden_size": 1024, "intermediate_size": 1024,
               "num_attention_heads": 8, "num_key_value_heads": 8, "kv_lora_rank": 256,
               "qk_nope_head_dim": 64, "qk_rope_head_dim": 32, "v_head_dim": 64,
               "moe_intermediate_size": 256, "n_routed_experts": 32, "n_routed_experts_held": 8,
               "num_experts_per_tok": 6, "n_shared_experts": 1, "vocab_size": 16384},
    "traffic": {"batch": 8, "prompt_len": 16, "new_tokens": 48, "blocks_per_seq": 8,
                "block_size": 8, "check_sequences": 8},
}


def test_control_is_not_correct():
    """Over 384 served tokens per seed, the fp8 control puts first more
    tokens off the reference's best than the cell's limit allows; the
    program does not, in any one sequence, and neither does the bfloat16
    witness (the reference with its matmul operands rounded to bfloat16)."""
    bench = harness.Bench(ROOT)
    c = bench.cell(CELL)
    cfg = {**bench.config(c["config"]), **CONTROL["config"]}
    mix = {**bench.traffic(c["traffic"]), **CONTROL["traffic"]}
    lines = list(bench.driver("decode_mla_moe").readings(
        cfg, mix, [4, 2**31 + 1, 99], jax.devices(), bench.reference(cfg["reference"])))
    limit = mix["limits"]["off_best_share"]
    assert all(ln["program"]["sequence_share_max"] <= limit for ln in lines), lines
    assert all(ln["witness"]["off_best_share"] <= limit for ln in lines), lines
    assert all(ln["control"]["off_best_share"] > limit for ln in lines), lines
