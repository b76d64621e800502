"""Every named scope of the simulation walk and the paged decode step reaches
the lowered program, as an op_name path segment of its ops.

The trace reduction by scope (bench/scopes.py) finds each phase on the chip
by these names, so a refactor that drops one shows here
first. Lowering only: nothing is compiled or run.
"""
import re
from unittest import mock

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_reduced_config
from repro.engine import simloop
from repro.kernels.rainbow_attention import ops as ra_ops
from repro.memory.kvcache import PagedConfig, paged_init, paged_scales_init
from repro.models import model as M
from repro.serving.rainbow_decode import rainbow_decode_step
from repro.sim.config import MachineConfig
from repro.workloads.scenarios import probe_meta

WALK = {"tlb", "tlb4k", "tlb2m", "bmc"}
CONTROL = {"observe", "plan", "apply"}
DECODE = {"translate", "layers", "qkv", "read", "attend", "mlp", "append", "observe",
          "promote", "logits"}
MLA = DECODE - {"read"} | {"absorb", "route", "experts", "shared"}
SCENARIO, ACCESSES = "syn/GUPS", 256

# (case, EngineSpec keywords, the scopes its fused program must carry)
ENGINE_CASES = [
    ("rainbow", {"policy": "rainbow"}, {"synth"} | WALK | CONTROL),
    ("rainbow-reference-walk", {"policy": "rainbow", "fastpath": False},
     {"synth"} | WALK | CONTROL),
    ("rainbow-queueing", {"policy": "rainbow", "timing_model": "queueing"},
     {"synth"} | WALK | CONTROL | {"queue"}),
    ("nomad", {"policy": "nomad"}, {"synth"} | WALK | CONTROL),
    ("hscc-4kb-mig", {"policy": "hscc-4kb-mig"}, {"synth", "tlb", "tlb4k", "plan", "apply"}),
    ("hscc-2mb-mig", {"policy": "hscc-2mb-mig"}, {"synth", "tlb", "tlb2m", "plan"}),
    ("flat-static", {"policy": "flat-static"}, {"synth", "tlb", "tlb4k"}),
]
DECODE_CASES = [("decode-full", {}, DECODE), ("decode-sparse", {"mode": "sparse"}, DECODE),
                ("decode-int8", {"quantize": True}, DECODE),
                # where the rainbow_attention kernel reads the pools (a TPU picks
                # it; forced here), it runs under "attend/paged_attention"
                ("decode-full-kernel", {"kernel": True},
                 DECODE - {"read"} | {"paged_attention"}),
                # a latent-attention MoE model: absorbed projections, the
                # latent read inside "attend", the MoE phases; its layer 0 is
                # dense ("mlp")
                ("decode-mla", {"arch": "moonlight-16b-a3b"}, MLA),
                ("decode-mla-kernel", {"arch": "moonlight-16b-a3b", "kernel": True},
                 MLA | {"latent_attention"})]


def scope_segments(lowered) -> set[str]:
    """Every "/" segment of the op names (name locations) of a lowered program."""
    text = lowered.as_text(debug_info=True)
    return {seg for name in re.findall(r'loc\("([^"]+)"\(', text) for seg in name.split("/")}


def _engine(kw):
    meta = probe_meta(SCENARIO, ACCESSES)
    spec = simloop.EngineSpec(
        mc=MachineConfig(), num_superpages=meta["num_superpages"],
        footprint_pages=meta["footprint_pages"],
        source=simloop.TraceSource(scenario=SCENARIO, accesses=ACCESSES), **kw)
    state = jax.eval_shape(lambda: simloop.engine_init(spec))
    return simloop._engine_run_fused_jit.lower(spec, state, jnp.int32(3), 2)


def _decode(kw):
    cfg = get_reduced_config(kw.get("arch", "qwen3-4b"))
    b, s = 2, 16
    pcfg = PagedConfig(block_size=4, blocks_per_seq=s // 4, hot_slots=2, top_n=2,
                       max_promotions=2, interval_steps=2,
                       quantize=kw.get("quantize", False))
    params = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0), tp=1))
    kv = jax.eval_shape(lambda: paged_init(cfg, pcfg, b, 1, cfg.num_layers))
    tok = jax.ShapeDtypeStruct((b, 1), jnp.int32)
    mode = kw.get("mode", "full")
    if pcfg.quantize:
        sc = jax.eval_shape(lambda: paged_scales_init(pcfg, b, cfg.kv_store(1), cfg.num_layers))
        step = jax.jit(lambda p, t, k, x: rainbow_decode_step(cfg, pcfg, p, t, k, mode=mode,
                                                               scales=x))
        return step.lower(params, tok, kv, sc)
    step = jax.jit(lambda p, t, k: rainbow_decode_step(cfg, pcfg, p, t, k, mode=mode))
    if kw.get("kernel"):
        with mock.patch.object(ra_ops, "backend", lambda *a, **k: "interpret"):
            return step.lower(params, tok, kv)
    return step.lower(params, tok, kv)


@pytest.mark.parametrize(
    "case,build,kw,want",
    [(c, _engine, kw, want) for c, kw, want in ENGINE_CASES]
    + [(c, _decode, kw, want) for c, kw, want in DECODE_CASES],
    ids=[c for c, _, _ in ENGINE_CASES + DECODE_CASES])
def test_every_scope_reaches_the_program(case, build, kw, want):
    segments = scope_segments(build(kw))
    assert want <= segments, f"{case}: scopes missing from op names: {sorted(want - segments)}"
