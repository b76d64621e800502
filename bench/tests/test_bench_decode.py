"""The paged-decode cell's driver at a size a CPU holds, wide and deep enough
that the next token depends on the context: a sound run is correct, the
timed path broken underneath is not, and neither is the control (the
reference in fp8, a precision below the bfloat16 the configuration states)."""
import dataclasses
import pathlib
import time

import jax
import jax.numpy as jnp
import pytest

from bench import harness

ROOT = pathlib.Path(__file__).resolve().parents[2]
CELL = "decode-qwen3-0.6b-paged"
SMALL = {
    "config": {"num_hidden_layers": 8, "hidden_size": 512, "intermediate_size": 1536,
               "num_attention_heads": 8, "num_key_value_heads": 4, "head_dim": 64,
               "vocab_size": 32768},
    "traffic": {"batch": 2, "prompt_len": 16, "new_tokens": 32, "blocks_per_seq": 16,
                "block_size": 4, "check_sequences": 2},
}


def run(traced=False, seed=2**31 + 5):
    jax.clear_caches()  # a patched program must be traced again
    return harness.run_cell(ROOT, CELL, seed, 0.1, traced, t_start=time.perf_counter(),
                            require_chip=False, overrides=SMALL, log=lambda *a, **k: None)


@pytest.mark.parametrize("traced", [False, True])
def test_sound_run_is_correct(traced):
    line = run(traced)
    assert line["correct"], line["checks"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    if traced:
        # the roofline and the MFU need the chip's peaks; these read on the CPU
        assert set(line["metrics"]) == {"decode.compile_s_per_call", "decode.device_idle_share"}
        assert line["device"]["busy_s"] > 0 and "breakdown" in line
    else:
        assert set(line["metrics"]) == {"decode_tokens_per_s", "setup_s"}


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

    def half(cfg, params, prompt, new_tokens, pcfg=None):
        gen = real(cfg, params, prompt[: prompt.shape[0] // 2], new_tokens, pcfg)
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


def test_control_is_not_correct():
    """Over 512 served tokens per seed, the fp8 control puts first at least
    one token that the reference ranks further below its best than the
    cell's limit allows, as it does at the cell's own size on the chip."""
    bench = harness.Bench(ROOT)
    c = bench.cell(CELL)
    cfg = {**bench.config(c["config"]), **SMALL["config"]}
    mix = {**bench.traffic(c["traffic"]), "batch": 8, "prompt_len": 32, "new_tokens": 64,
           "blocks_per_seq": 32, "block_size": 4, "check_sequences": 8}
    lines = list(bench.driver("decode").readings(cfg, mix, [4, 2**31 + 1, 99], jax.devices(),
                                                 bench.reference(cfg["reference"])))
    limit = mix["limits"]["widest_gap"]
    assert all(ln["program"]["widest_gap"] <= limit for ln in lines), lines
    assert all(ln["control"]["widest_gap"] > limit for ln in lines), lines
