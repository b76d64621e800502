"""Compile-only guards: the main path's Pallas kernels at real widths, compiled
for a described (not attached) TPU v5e chip.

Interpret mode cannot see what the TPU compiler refuses: blocks off the
(8, 128) tiling, or more fast memory than a kernel may use. Each case here
compiles one kernel ahead of time and checks that the program really holds
a Mosaic kernel (`tpu_custom_call`). The topology is described inside a
fixture, so collecting this file never loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.block_gather.block_gather import block_gather
from repro.kernels.page_counter.page_counter import (
    fused_observe_count,
    two_stage_count,
)
from repro.sim.config import PAGES_PER_SP
from repro.workloads.scenarios import probe_meta

ACCESSES = 320_000  # syn/GUPS's calibrated accesses per interval
MONITORED = 100  # MachineConfig().top_n
KV_BLOCK = 8  # launch.serve's --block-size


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


@pytest.mark.parametrize("case", [
    pytest.param(_counter_case(fused_observe_count, jnp.bool_),
                 id="fused_observe_count"),
    pytest.param(_counter_case(two_stage_count, jnp.uint32),
                 id="two_stage_count"),
    pytest.param(_block_gather_case, id="block_gather"),
])
def test_kernel_compiles_for_v5e(case, one_chip):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = case(sds)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
