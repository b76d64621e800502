"""Compile-only guards: the main path's Pallas kernels at real widths, compiled
for a described (not attached) TPU v5e chip.

Interpret mode cannot see what the TPU compiler refuses: blocks off the
(8, 128) tiling, or more fast memory than a kernel may use. Each case here
compiles one kernel ahead of time and checks that the program really holds
a Mosaic kernel (`tpu_custom_call`). The topology is described inside a
fixture, so collecting this file never loads the TPU compiler.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.block_gather.block_gather import block_gather
from repro.kernels.rainbow_attention import ops as ra_ops
from repro.kernels.rainbow_attention.rainbow_attention import rainbow_attention
from repro.kernels.page_counter.page_counter import (
    fused_observe_count,
    two_stage_count,
)
from repro.sim.config import PAGES_PER_SP
from repro.workloads.scenarios import probe_meta

ACCESSES = 320_000  # syn/GUPS's calibrated accesses per interval
MONITORED = 100  # MachineConfig().top_n
KV_BLOCK = 8  # launch.serve's --block-size
# the paged decode cell: batch 8, 256 blocks of 16 tokens per sequence, a hot
# pool of 128 blocks (build_paged_config(256, 16))
DECODE_BATCH, DECODE_NBLK, DECODE_BLOCK, DECODE_HOT = 8, 256, 16, 128


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a chip-targeted executable cannot be read back here: keep it out of
    # the persistent cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _counter_case(kernel, operand_dtype):
    nsp = probe_meta("syn/Graph500")["num_superpages"]

    def build(sds):
        fn = lambda sp, page, x, mon: kernel(  # noqa: E731
            sp, page, x, mon, nsp, PAGES_PER_SP, interpret=False)
        return fn, (sds((ACCESSES,), jnp.int32), sds((ACCESSES,), jnp.int32),
                    sds((ACCESSES,), operand_dtype), sds((MONITORED,), jnp.int32))

    return build


def _block_gather_case(sds):
    cfg = get_config("qwen3-0.6b")
    batch, nblk = 4, 8  # the chip smoke's decode: 4 x (32 + 32) tokens
    hot, lanes = 8, 8  # serving-default hot_slots / max_promotions there
    block = (KV_BLOCK, cfg.num_kv_heads, cfg.head_dim)
    fn = lambda cap, hot_pool, src, dst: block_gather(  # noqa: E731
        cap, hot_pool, src, dst, interpret=False)
    return fn, (sds((batch * nblk, *block), jnp.bfloat16),
                sds((hot, *block), jnp.bfloat16),
                sds((lanes,), jnp.int32), sds((lanes,), jnp.int32))


def _rainbow_attention_case(sds):
    cfg = get_config("qwen3-0.6b")
    blk = (DECODE_BLOCK, cfg.num_kv_heads, cfg.head_dim)
    cap = sds((cfg.num_layers, DECODE_BATCH * DECODE_NBLK, *blk), jnp.bfloat16)
    hot = sds((cfg.num_layers, DECODE_HOT, *blk), jnp.bfloat16)
    fn = lambda *a: rainbow_attention(*a, interpret=False)  # noqa: E731
    return fn, (sds((DECODE_BATCH, cfg.num_heads, cfg.head_dim), jnp.bfloat16),
                cap, cap, hot, hot, sds((DECODE_BATCH, DECODE_NBLK), jnp.int32),
                sds((), jnp.int32), sds((), jnp.int32))


@pytest.mark.parametrize("case", [
    pytest.param(_counter_case(fused_observe_count, jnp.bool_),
                 id="fused_observe_count"),
    pytest.param(_counter_case(two_stage_count, jnp.uint32),
                 id="two_stage_count"),
    pytest.param(_block_gather_case, id="block_gather"),
    pytest.param(_rainbow_attention_case, id="rainbow_attention"),
])
def test_kernel_compiles_for_v5e(case, one_chip):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = case(sds)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_paged_decode_step_compiles_for_v5e(one_chip, monkeypatch):
    """The whole decode step at the cell's widths: the layer loop calls the
    rainbow_attention kernel and holds no operation of pool scale — no
    gather, conversion or broadcast over all 4,096 provisioned positions, no
    per-layer slice or concatenation of the pools."""
    from repro.launch.serve import build_paged_config
    from repro.memory.kvcache import paged_init
    from repro.models import model as M
    from repro.serving.rainbow_decode import rainbow_decode_step

    # on this CPU host ops.backend would pick the jnp read
    monkeypatch.setattr(ra_ops, "backend", lambda *a, **k: "pallas")
    cfg = dataclasses.replace(get_config("qwen3-0.6b"), tie_embeddings=True)
    pcfg = build_paged_config(DECODE_NBLK, DECODE_BLOCK)
    params = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0), tp=1))
    kv = jax.eval_shape(lambda: paged_init(cfg, pcfg, DECODE_BATCH, 1, cfg.num_layers))
    on_chip = lambda t: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), t)
    tokens = jax.ShapeDtypeStruct((DECODE_BATCH, 1), jnp.int32, sharding=one_chip)
    step = jax.jit(lambda p, t, k: rainbow_decode_step(cfg, pcfg, p, t, k))
    text = step.lower(on_chip(params), tokens, on_chip(kv)).compile().as_text()
    assert "tpu_custom_call" in text
    positions = DECODE_NBLK * DECODE_BLOCK
    per_layer_pool = (DECODE_BATCH * DECODE_NBLK, DECODE_BLOCK, cfg.num_kv_heads,
                      cfg.head_dim)
    for shape in (f"[{DECODE_BATCH},{positions}", f"[{DECODE_BATCH},{positions + 1}",
                  "[{},{},{},{}]".format(*per_layer_pool),
                  "[{},{},{},{}]".format(per_layer_pool[0] + DECODE_HOT,
                                         *per_layer_pool[1:])):
        assert not re.search(r"\w+" + re.escape(shape), text), shape


# the latent-attention MoE decode cell: batch 128, 128 blocks of 16 tokens
# per sequence, a hot pool of 64 blocks (build_paged_config(128, 16)); layer 0
# dense and 8 MoE layers holding 16 of 64 experts
MLA_BATCH, MLA_NBLK, MLA_LAYERS, MLA_HELD = 128, 128, 9, 16


def _computations(text):
    """{computation name: its HLO text} of a module's text."""
    out, name = {}, None
    for line in text.split("\n"):
        m = re.match(r"(?:ENTRY )?%(\S+) ", line)
        if m and line.rstrip().endswith("{"):
            name = m.group(1)
            out[name] = []
        elif name is not None:
            out[name].append(line)
    return {k: "\n".join(v) for k, v in out.items()}


def test_mla_moe_decode_step_compiles_for_v5e(one_chip, monkeypatch):
    """The latent-attention MoE decode step at the cell's widths: the MoE
    layer loop's body calls the kernel's latent mode once, the dense layer
    once more, and nothing gathers, slices or concatenates latent rows at
    the provisioned capacity (2,048 positions per sequence)."""
    from repro.launch.serve import build_paged_config
    from repro.memory.kvcache import paged_init
    from repro.models import model as M
    from repro.serving.rainbow_decode import rainbow_decode_step

    monkeypatch.setattr(ra_ops, "backend", lambda *a, **k: "pallas")
    cfg = dataclasses.replace(get_config("moonlight-16b-a3b"), num_layers=MLA_LAYERS,
                              moe_experts_held=MLA_HELD)
    pcfg = build_paged_config(MLA_NBLK, DECODE_BLOCK)
    params = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0), tp=1))
    kv = jax.eval_shape(lambda: paged_init(cfg, pcfg, MLA_BATCH, 1, cfg.num_layers))
    on_chip = lambda t: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), t)
    tokens = jax.ShapeDtypeStruct((MLA_BATCH, 1), jnp.int32, sharding=one_chip)
    step = jax.jit(lambda p, t, k: rainbow_decode_step(cfg, pcfg, p, t, k,
                                                       collect_slots=True))
    text = step.lower(on_chip(params), tokens, on_chip(kv)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    comps = _computations(text)
    bodies = [comps[b] for b in re.findall(r"body=%([\w.\-]+)", text)]
    assert sum('custom_call_target="tpu_custom_call"' in b for b in bodies) == 1
    # (the hidden state is [128, 2048] too: a latent read over the capacity
    # would be [128, 2048, (1,) 640] rows or [128, 16, 2048] scores)
    b, n, w, h = MLA_BATCH, MLA_NBLK * DECODE_BLOCK, cfg.latent_width, cfg.num_heads
    for shape in (f"[{b},{n},1,{w}]", f"[{b},{n},{w}]", f"[{b},{h},{n}]", f"[{b},{n + 1}",
                  f"[{b},{h},{n + 1}]", f"[{b * MLA_NBLK},{DECODE_BLOCK},",
                  f"[{b * MLA_NBLK + pcfg.hot_slots},{DECODE_BLOCK},"):
        assert not re.search(r"\w+" + re.escape(shape), text), shape
