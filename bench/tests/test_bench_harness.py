"""The harness without a chip: the contract of BENCHMARK.json, cells found by
name from files, the refusal to run without a TPU, the peak table, the trace
reduction and the decode arithmetic."""
import json
import os
import pathlib
import subprocess
import sys
import textwrap
import time

import pytest

from bench import flops, harness, peaks, trace

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).resolve().parent / "data"


def _spec():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_benchmark_json_names_files_and_metrics():
    spec = _spec()
    assert set(spec) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    bench = harness.Bench(ROOT)
    names = [e["name"] for key in ("configs", "workloads", "end_to_end", "per_layer")
             for e in spec[key]]
    assert len(names) == len(set(names))
    assert all(harness.NAME.match(n) for n in names)
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        cfg = bench.config(c["name"])
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
        assert (ROOT / cfg["reference"]).is_file()
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25 and "workloads" not in e2e["setup_s"]
    for cell in spec["workloads"]:
        assert set(cell) == {"name", "config", "traffic", "chips", "why"}
        assert len(cell["why"]) <= 200 and cell["chips"] in (1, 4)
        mix = bench.traffic(cell["traffic"])
        assert (bench.dir / "drivers" / f"{mix['driver']}.py").is_file()
        reported = {m["name"] for m in bench.end_to_end(cell["name"])}
        assert mix["rate_metric"] in reported and "setup_s" in reported and len(reported) >= 2
        layer = bench.per_layer(cell["name"])
        assert layer and all(m["moves"] in reported for m in layer)
    for m in spec["per_layer"]:
        assert (bench.dir / "metrics" / f"{m['name']}.py").is_file()
        assert all(w in {c["name"] for c in spec["workloads"]} for w in m["workloads"])
        if m["unit"] == "%" and ("roofline" in m["name"] or "mfu" in m["name"]):
            assert m["better"] == "higher"


def _write(root: pathlib.Path, rel: str, text: str) -> None:
    p = root / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(text))


def test_a_new_cell_is_new_files_only(tmp_path):
    """A configuration, a mix, a driver and a metric reader added as files
    run through the harness unchanged; nothing of bench/ is edited."""
    spec = {
        "command": ["python3", "bench/run.py"], "paths": ["bench"], "run_seconds": 1,
        "configs": [{"name": "toy", "source": "https://example.org/toy",
                     "file": "bench/configs/toy.json", "reduced": [], "why": "a toy"}],
        "workloads": [{"name": "toy-cell", "config": "toy", "traffic": "toy-mix",
                       "chips": 1, "why": "a toy"}],
        "end_to_end": [
            {"name": "toy_items_per_s", "unit": "items/s", "better": "higher", "bound": 0.05,
             "source": "host_clock", "workloads": ["toy-cell"]},
            {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25,
             "source": "host_clock"}],
        "per_layer": [{"name": "toy.busy_share", "unit": "%", "better": "higher",
                       "source": "device_trace", "layer": "toy", "moves": "toy_items_per_s"}],
    }
    _write(tmp_path, "BENCHMARK.json", json.dumps(spec))
    _write(tmp_path, "bench/configs/toy.json", json.dumps(
        {"source": "https://example.org/toy", "reduced": [], "n": 384,
         "reference": "bench/reference/toy.py"}))
    _write(tmp_path, "bench/reference/toy.py", """
        def answer(n):
            return float(n ** 3)
        """)
    _write(tmp_path, "bench/traffic/toy-mix.json", json.dumps(
        {"driver": "toy", "work_unit": "items", "rate_metric": "toy_items_per_s"}))
    _write(tmp_path, "bench/drivers/toy.py", """
        import jax.numpy as jnp
        class Driver:
            span = "toy"
            def __init__(self, cfg, mix, seed, devices, reference):
                self.n, self.ref, self.got = cfg["n"], reference, []
            def unit(self):
                x = jnp.ones((self.n, self.n))
                self.got.append(float((x @ x).sum()))
                return 1
            def counters(self):
                return {"units": len(self.got)}
            def release(self):
                pass
            def check(self):
                gap = max(abs(g - self.ref.answer(self.n)) for g in self.got)
                return {"gap": {"value": gap, "limit": 0}}, 0
        """)
    _write(tmp_path, "bench/metrics/toy.busy_share.py", """
        def read(ctx):
            t = ctx["trace"]
            return 100.0 * t["busy_s"] / t["window_s"] if t["busy_s"] > 0 else None
        """)
    for traced in (False, True):
        line = harness.run_cell(tmp_path, "toy-cell", 7, 0.2, traced, t_start=time.perf_counter(),
                                require_chip=False, log=lambda *a, **k: None)
        assert line["correct"] and line["attempted"] >= 1 and line["failed"] == 0
        assert list(line)[-1] == "checks"
        want = {"toy.busy_share"} if traced else {"toy_items_per_s", "setup_s"}
        assert set(line["metrics"]) == want
        if traced:
            assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]


def test_run_refuses_without_a_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload", "sim-gups-flat-static",
         "--seed", str(2**31 + 11), "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300, cwd=ROOT)
    assert out.returncode != 0
    assert "{" not in out.stdout
    assert "TPU" in out.stderr


def test_peaks_refuse_an_unknown_device_kind():
    assert peaks.peaks("TPU v5 lite")["hbm_bw"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("cpu")


def test_trace_reduction_of_a_recorded_cpu_trace():
    red = trace.reduce(trace.load(DATA), "cpu")
    assert red["chips"] == 1
    assert 0 < red["busy_s"] < red["window_s"]
    assert red["spans"]["bench.unit"] == 3
    longest_label, longest = red["idle_gaps"][0]
    assert longest >= 0.045 and longest_label in ("$time sleep", "bench.idle")
    assert sum(s for _, s in red["device_ops"]) >= red["busy_s"] * 0.999
    assert red["window_s"] - red["busy_s"] >= longest


def test_merge_is_a_clipped_union():
    assert trace.merge([(5, 9), (0, 2), (1, 3), (8, 12)], 1, 10) == [(1, 3), (5, 10)]
    assert trace.merge([(0, 1)], 2, 3) == []


def test_decode_arithmetic_by_hand():
    cfg = json.loads((ROOT / "bench" / "configs" / "qwen3-0.6b.json").read_text())
    ops0, bytes0 = flops.decode_step(cfg, 1, 0)
    params = 28 * (1024 * 16 * 128 * 2 + 2 * 1024 * 8 * 128 + 3 * 1024 * 3072) + 151936 * 1024
    norms = 28 * (2 * 1024 + 2 * 128) + 1024
    assert params + norms == 596_049_920  # the published parameter count
    assert ops0 == 2 * params + 4 * 28 * 16 * 128
    kv = 28 * 2 * 8 * 128 * 2
    assert kv == 114_688
    ops9, bytes9 = flops.decode_step(cfg, 8, 9)
    assert bytes9 - bytes0 == pytest.approx(8 * 9 * kv + 7 * kv + 7 * 151936 * 4)
    tot_ops, tot_bytes = flops.decode_call(cfg, 8, 3)
    assert tot_ops == sum(flops.decode_step(cfg, 8, n)[0] for n in range(3))
