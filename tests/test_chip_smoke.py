"""chip_smoke.py's phases at tiny sizes on the CPU, and its refusal to run
without a TPU. The phases are the same functions the chip runs; only the
sizes, the model config and the kernel mode (interpret) differ."""
import importlib.util
import os
import subprocess
import sys
import textwrap

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_phase_a_tiny():
    out = _smoke().phase_a(accesses=3000, intervals=2, cpu_intervals=1,
                           kernel_backend="interpret")
    assert out["interpret_eq_jax"] == "bitwise"
    assert out["device_vs_cpu_max_rel_cycle_diff"] == 0.0  # CPU vs CPU
    assert set(out["runs"]) == {
        "syn/GUPS/rainbow", "syn/GUPS/hscc-4kb-mig", "syn/GUPS/nomad",
        "syn/GUPS/flat-static", "syn/GUPS/rainbow/constrained",
        "syn/Graph500/rainbow",
    }
    assert all(r["ipc"] > 0 for r in out["runs"].values())
    assert out["runs"]["syn/GUPS/flat-static"]["migrations"] == 0


def test_phase_b_tiny():
    from repro.configs import get_reduced_config

    out = _smoke().phase_b(get_reduced_config("qwen3-0.6b"), batch=2,
                           prompt_len=8, new_tokens=16, block_size=4)
    assert out["promoted_hot_blocks"] > 0
    assert out["max_abs_logit_diff"] <= out["logit_atol"]


def test_phase_c_on_4_virtual_devices():
    script = textwrap.dedent("""
        import importlib.util
        spec = importlib.util.spec_from_file_location("chip_smoke", "chip_smoke.py")
        cs = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(cs)
        out = cs.phase_c(devices=4, accesses=2000, intervals=2)
        assert out["cells"] == 12 and out["groups"] == 4, out
        print("PHASE_C_OK")
    """)
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, env=env, cwd=ROOT, timeout=600)
    assert "PHASE_C_OK" in out.stdout, out.stderr[-2000:]


def test_main_refuses_to_run_without_a_tpu(capsys):
    assert _smoke().main([]) != 0
    captured = capsys.readouterr()
    assert "needs a TPU" in captured.err
    assert captured.out == ""  # no result line of any kind
