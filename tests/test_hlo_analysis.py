"""HLO-text collective parser + roofline terms (launch/hlo_analysis)."""
import jax
import jax.numpy as jnp
import pytest

from repro.launch import hlo_analysis as H


def test_shape_bytes():
    assert H.shape_bytes("f32[128,256]{1,0}") == 128 * 256 * 4
    assert H.shape_bytes("bf16[2,3]") == 12
    assert H.shape_bytes("(f32[4], s8[8])") == 16 + 8
    assert H.shape_bytes("pred[]") == 1


def test_collective_bytes_from_synthetic_hlo():
    hlo = """
HloModule m
ENTRY e {
  %p0 = f32[1024,32]{1,0} parameter(0)
  %ar = f32[1024,32]{1,0} all-reduce(%p0), replica_groups={}
  %ag = f32[2048,32]{1,0} all-gather(%ar), dimensions={0}
  %x = f32[1024,32]{1,0} add(%p0, %ar)
}
"""
    stats = H.collective_bytes(hlo)
    assert stats.bytes_by_op["all-reduce"] == 1024 * 32 * 4
    assert stats.bytes_by_op["all-gather"] == 1024 * 32 * 4  # operand size
    assert stats.count_by_op["all-reduce"] == 1


def test_collective_bytes_on_real_compiled_module():
    """End-to-end: psum over a 1-device mesh still emits an all-reduce."""
    mesh = jax.make_mesh((1,), ("data",))
    from jax.sharding import NamedSharding, PartitionSpec as P

    def f(x):
        return jax.lax.with_sharding_constraint(
            x.sum(keepdims=True), NamedSharding(mesh, P())
        )

    with mesh:
        c = jax.jit(f).lower(jax.ShapeDtypeStruct((64, 64), jnp.float32)).compile()
    stats = H.collective_bytes(c.as_text())
    assert isinstance(stats.total_bytes, int)  # parser runs on real HLO


def test_roofline_terms_dominance():
    # 1 s compute, 2 s memory on one v5e chip
    t = H.roofline_terms(197e12, 819e9 * 2, 0, device_kind="TPU v5 lite")
    assert t["dominant"] == "memory_s"
    assert abs(t["roofline_fraction"] - 0.5) < 1e-6


def test_roofline_terms_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        H.roofline_terms(1.0, 1.0, 0, device_kind="cpu")


def test_decode_bytes_global_sane():
    from repro.configs import get_config, get_shape

    cfg = get_config("qwen3-4b")
    shape = get_shape("decode_32k")
    b = H.decode_bytes_global(cfg, shape)
    # params (~8 GB) + KV sweep (~1.2 TB global at kv_store=16)
    assert 0.5e12 < b < 2.5e12
    # sliding-window arch reads far less
    hy = H.decode_bytes_global(get_config("hymba-1.5b"), get_shape("long_500k"))
    assert hy < b
