"""Per-architecture smoke tests (deliverable f): reduced config, one forward +
one train step on CPU, asserting output shapes + no NaNs."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCH_IDS, get_config, get_reduced_config
from repro.models import model as M
from repro.train.step import TrainStepConfig, build_train_step, init_train_state


def _batch(cfg, key, b=2, s=32):
    batch = {
        "tokens": jax.random.randint(key, (b, s), 0, cfg.vocab_size),
        "targets": jax.random.randint(key, (b, s), 0, cfg.vocab_size),
        "loss_mask": jnp.ones((b, s), jnp.float32),
    }
    if cfg.family == "vlm":
        nv = cfg.num_vision_tokens
        for k in ("tokens", "targets", "loss_mask"):
            batch[k] = batch[k][:, : s - nv]
        batch["vision_embeds"] = jax.random.normal(key, (b, nv, cfg.d_model))
    if cfg.is_encoder_decoder:
        se = s // cfg.encoder_seq_divisor
        for k in ("tokens", "targets", "loss_mask"):
            batch[k] = batch[k][:, : s - se]
        batch["frames"] = jax.random.normal(key, (b, se, cfg.d_model))
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_shapes_and_finite(arch):
    cfg = get_reduced_config(arch)
    key = jax.random.PRNGKey(0)
    params = M.init_params(cfg, key, tp=1)
    batch = _batch(cfg, key)
    logits = M.forward(cfg, params, batch)
    assert logits.shape[:2] == batch["targets"].shape
    assert logits.shape[-1] == cfg.padded_vocab
    assert bool(jnp.isfinite(logits).all())


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_one_train_step(arch):
    cfg = get_reduced_config(arch)
    key = jax.random.PRNGKey(1)
    tcfg = TrainStepConfig(tp=1, remat="none")
    state = init_train_state(cfg, key, tcfg)
    step = jax.jit(build_train_step(cfg, tcfg))
    batch = _batch(cfg, key)
    state2, metrics = step(state, batch)
    assert bool(jnp.isfinite(metrics["loss"]))
    assert bool(jnp.isfinite(metrics["grad_norm"]))
    # params actually changed
    before = jax.tree.leaves(state["params"])[0]
    after = jax.tree.leaves(state2["params"])[0]
    assert not jnp.array_equal(before, after)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_full_config_construction(arch):
    """Full (unreduced) configs are valid and sized right (no allocation)."""
    cfg = get_config(arch)
    n = cfg.param_count()
    expected = {
        "qwen3-4b": (3.5e9, 5.5e9),
        "qwen3-0.6b": (0.5e9, 0.9e9),
        "smollm-360m": (0.25e9, 0.50e9),
        "granite-8b": (7e9, 9e9),
        "deepseek-moe-16b": (14e9, 19e9),
        "qwen2-moe-a2.7b": (13e9, 16e9),
        "whisper-medium": (0.6e9, 1.0e9),
        "hymba-1.5b": (1.2e9, 2.2e9),
        "internvl2-2b": (1.7e9, 2.6e9),
        "mamba2-1.3b": (1.0e9, 1.8e9),
        "moonlight-16b-a3b": (15e9, 17e9),
    }[arch]
    assert expected[0] <= n <= expected[1], f"{arch}: {n:.3e}"
    # padded heads divide cleanly under tp=16 (the production mesh)
    if not cfg.attn_free:
        assert cfg.padded_heads(16) % 16 == 0
        assert cfg.padded_heads(16) % cfg.kv_store(16) == 0
    assert cfg.padded_vocab % 256 == 0 or cfg.vocab_pad_multiple != 256
