"""Elastic checkpoint restore across DIFFERENT mesh shapes (subprocess with 8
host devices — the scale-up/scale-down restart path of DESIGN.md §5)."""
import os
import subprocess
import sys
import textwrap


def test_save_on_4x2_restore_on_2x2(tmp_path):
    script = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint import restore_state, save_state

        ckpt = {str(tmp_path)!r}
        state = {{"w": jnp.arange(64.0).reshape(8, 8),
                  "m": jnp.ones((8, 8)) * 3}}

        # "job 1": 4x2 mesh, sharded state
        mesh1 = jax.make_mesh((4, 2), ("data", "model"))
        sh1 = {{"w": NamedSharding(mesh1, P("data", "model")),
               "m": NamedSharding(mesh1, P("data", None))}}
        state1 = jax.tree.map(lambda a, s: jax.device_put(a, s), state, sh1)
        save_state(ckpt, 7, state1)

        # "job 2": relaunched at HALF the devices, different layout
        mesh2 = jax.make_mesh((2, 2), ("data", "model"))
        sh2 = {{"w": NamedSharding(mesh2, P("model", "data")),
               "m": NamedSharding(mesh2, P(None, "data"))}}
        like = jax.eval_shape(lambda: state)
        restored, step = restore_state(ckpt, like, shardings=sh2)
        assert step == 7
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.arange(64.0).reshape(8, 8))
        assert restored["w"].sharding.spec == P("model", "data")
        assert len(restored["w"].sharding.device_set) == 4
        print("ELASTIC_OK")
    """)
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        env=env, cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=300,
    )
    assert "ELASTIC_OK" in out.stdout, out.stderr[-2000:]


def test_sharded_train_step_on_4x2_mesh(tmp_path):
    """Full train step (TP=2, DP=4, ZeRO specs) on 8 real host devices."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from repro.configs import get_reduced_config
        from repro.data.pipeline import SyntheticLM
        from repro.launch.sharding import make_constrainer, sharding_tree
        from repro.train.step import (TrainStepConfig, batch_specs,
                                      build_train_step, init_train_state,
                                      train_state_specs)

        from repro.launch.mesh import make_test_mesh

        cfg = get_reduced_config("qwen3-4b")  # 4 heads, kv 2 -> TP=2 works
        mesh = make_test_mesh(model=2)  # (4, 2) over the 8 devices
        sc = make_constrainer(mesh)
        tcfg = TrainStepConfig(tp=2, remat="full")
        state = init_train_state(cfg, jax.random.PRNGKey(0), tcfg)
        state_sh = sharding_tree(train_state_specs(cfg, tcfg, dp_size=4), mesh)
        batch_sh = sharding_tree(batch_specs(cfg), mesh)
        data = SyntheticLM(cfg.vocab_size, 32, 8, seed=1)
        step = jax.jit(build_train_step(cfg, tcfg, sc=sc),
                       in_shardings=(state_sh, batch_sh),
                       out_shardings=(state_sh, None), donate_argnums=(0,))
        with mesh:
            state = jax.device_put(state, state_sh)
            losses = []
            for _ in range(3):
                batch = jax.device_put(data.next_batch(), batch_sh)
                state, metrics = step(state, batch)
                losses.append(float(metrics["loss"]))
        assert all(l == l for l in losses), losses  # finite
        assert losses[-1] < losses[0] + 0.5
        print("SHARDED_TRAIN_OK", losses)
    """)
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        env=env, cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=600,
    )
    assert "SHARDED_TRAIN_OK" in out.stdout, out.stderr[-2000:]
