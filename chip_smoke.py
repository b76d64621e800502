"""Chip smoke: both products' main paths once, on the TPU, through their
normal entry points.

    python chip_smoke.py             # phases A and B on one chip
    python chip_smoke.py --chips 4   # phase C alone, on four chips

Phase A  Layer A simulator: `simulate(..., fused=True)` at the calibrated
         accesses per interval, on syn/GUPS (rainbow, hscc-4kb-mig, nomad,
         flat-static, rainbow under the constrained queueing geometry) and
         syn/Graph500 (rainbow). Checks: the compiled Pallas counting kernel
         gives the same SimMetrics as the "jax" backend, bit for bit; a short
         rainbow run gives the same integer counts on the TPU as on the CPU.
Phase B  Layer B paged decode at the full width of qwen3-0.6b (random weights
         from a seed) through `launch.serve.generate`, paged and flat: the
         paged logits match the flat ones within a bf16 tolerance, and hot
         blocks were promoted.
Phase C  A fused SweepPlan through FleetRunner on a 4-chip fleet mesh, with
         3 cells per group so the padding path runs, bitwise equal row for
         row to `engine_run_fused_batch` on one chip.

Each phase prints one line with its results and its wall time, compilation
included. The last line is one JSON object:
{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}.
Without a TPU the script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import concurrent.futures
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))
# the CPU backend stays reachable for the device-vs-CPU check; the platform
# check in main() still refuses a run whose first device is not a TPU
if os.environ.get("JAX_PLATFORMS") and "cpu" not in os.environ["JAX_PLATFORMS"]:
    os.environ["JAX_PLATFORMS"] += ",cpu"

import jax  # noqa: E402
import numpy as np  # noqa: E402

GUPS, GRAPH500 = "syn/GUPS", "syn/Graph500"
GUPS_POLICIES = ("rainbow", "hscc-4kb-mig", "nomad", "flat-static")
SERVE_ARCH = "qwen3-0.6b"
# paged vs flat logits: both decode the same bf16 weights and KV values; only
# the order of the attention reductions differs
LOGIT_ATOL = 0.05
FLEET_SCENARIOS = ("stress/zipf-hotspot", "stress/seq-scan")
FLEET_POLICIES = ("rainbow", "flat-static")
FLEET_SEEDS = (0, 1, 2)  # 3 cells per group: four chips do not divide them


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, over all threads."""

    def __init__(self):
        self._lock = threading.Lock()
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_):
        if event.startswith("/jax/core/compile/"):
            with self._lock:
                self.seconds += duration

    def phase(self, name: str, fn, *args, **kwargs) -> None:
        """Run one phase; print its results, wall and compile seconds."""
        c0, t0 = self.seconds, time.perf_counter()
        out = fn(*args, **kwargs)
        line = {"phase": name, "wall_s": time.perf_counter() - t0,
                "compile_s": self.seconds - c0, **out}
        print(json.dumps(line, default=str), flush=True)


def _parallel(calls: dict) -> dict:
    """Run independent calls in threads, so that their compiles overlap.

    At most 5 at once: one compile of the engine program for the chip peaks
    near 3 GB of host memory."""
    with concurrent.futures.ThreadPoolExecutor(max_workers=5) as ex:
        futures = {k: ex.submit(f) for k, f in calls.items()}
        return {k: f.result() for k, f in futures.items()}


def _on_cpu(fn):
    def run():
        with jax.default_device(jax.devices("cpu")[0]):
            return fn()
    return run


_COUNTS = ("migrations", "evictions", "shootdowns", "mig_aborts", "mpki")
_CYCLES = ("total_cycles", "tlb_service_cycles", "bank_stall_cycles",
           "mig_stall_cycles")


def phase_a(accesses: int | None = None, intervals: int = 5,
            cpu_intervals: int = 2, kernel_backend: str = "pallas") -> dict:
    """Layer A: the simulator's programs, the kernel check, TPU == CPU."""
    from repro.sim.runner import simulate
    from repro.timing import get_geometry

    def sim(app, policy, n=intervals, **kw):
        return lambda: simulate(app, policy, intervals=n, accesses=accesses,
                                fused=True, **kw)

    calls = {f"{GUPS}/{p}": sim(GUPS, p) for p in GUPS_POLICIES}
    calls[f"{GUPS}/rainbow/constrained"] = sim(
        GUPS, "rainbow", timing_model="queueing",
        queue_geometry=get_geometry("constrained"))
    calls[f"{GRAPH500}/rainbow"] = sim(GRAPH500, "rainbow")
    calls["kernel"] = sim(GUPS, "rainbow", counter_backend=kernel_backend)
    calls["short"] = sim(GUPS, "rainbow", n=cpu_intervals)
    calls["short/cpu"] = _on_cpu(sim(GUPS, "rainbow", n=cpu_intervals))
    res = _parallel(calls)

    if res["kernel"] != res[f"{GUPS}/rainbow"]:
        raise AssertionError(
            f"counter_backend={kernel_backend!r} SimMetrics differ from 'jax': "
            f"{res['kernel']} vs {res[f'{GUPS}/rainbow']}")
    dev, cpu = res["short"], res["short/cpu"]
    counts = {k: (getattr(dev, k), getattr(cpu, k)) for k in _COUNTS}
    if any(a != b for a, b in counts.values()):
        raise AssertionError(f"device vs CPU counts differ: {counts}")
    cyc = [(getattr(dev, k), getattr(cpu, k)) for k in _CYCLES]
    cyc += [(dev.breakdown[k], cpu.breakdown[k]) for k in dev.breakdown]
    rel = max(abs(a - b) / max(abs(b), 1e-30) for a, b in cyc)

    runs = {
        k: {"ipc": m.ipc, "mpki": m.mpki, "migrations": m.migrations,
            "evictions": m.evictions, "mig_aborts": m.mig_aborts,
            "total_cycles": m.total_cycles}
        for k, m in res.items() if k.startswith("syn/")
    }
    return {"accesses": accesses or "calibrated", "intervals": intervals,
            "runs": runs, f"{kernel_backend}_eq_jax": "bitwise",
            "device_eq_cpu_counts": "equal",
            "device_vs_cpu_max_rel_cycle_diff": rel}


def phase_b(cfg, batch: int = 4, prompt_len: int = 32, new_tokens: int = 32,
            block_size: int = 8, seed: int = 0) -> dict:
    """Layer B: paged vs flat greedy decode through launch.serve.generate."""
    from repro.launch import serve
    from repro.models import model as M

    params = M.init_params(cfg, jax.random.PRNGKey(seed), tp=1)
    prompt = jax.random.randint(jax.random.PRNGKey(seed + 1),
                                (batch, prompt_len), 0, cfg.vocab_size)
    nblk = -(-(prompt_len + new_tokens) // block_size)
    pcfg = serve.build_paged_config(nblk, block_size)
    gen = _parallel({
        "flat": lambda: serve.generate(cfg, params, prompt, new_tokens),
        "paged": lambda: serve.generate(cfg, params, prompt, new_tokens, pcfg),
    })
    flat, paged = gen["flat"], gen["paged"]
    v = cfg.vocab_size
    ft, pt = np.asarray(flat.tokens), np.asarray(paged.tokens)
    fl = np.asarray(flat.logits[..., :v], np.float32)
    pl = np.asarray(paged.logits[..., :v], np.float32)
    # logits are comparable up to and including a sequence's first
    # disagreement: until then both caches were fed the same tokens
    err = 0.0
    for b in range(batch):
        differ = np.flatnonzero(ft[b] != pt[b])
        upto = differ[0] + 1 if differ.size else new_tokens
        err = max(err, float(np.abs(fl[b, :upto] - pl[b, :upto]).max()))
    if not np.isfinite(fl).all() or not np.isfinite(pl).all():
        raise AssertionError("non-finite logits")
    if err > LOGIT_ATOL:
        raise AssertionError(f"paged vs flat logits differ by {err} > {LOGIT_ATOL}")
    if not paged.promoted:
        raise AssertionError("the paged cache promoted no hot blocks")
    return {"arch": cfg.name, "layers": cfg.num_layers, "d_model": cfg.d_model,
            "batch": batch, "prompt": prompt_len, "new_tokens": new_tokens,
            "max_abs_logit_diff": err, "logit_atol": LOGIT_ATOL,
            "token_agreement": f"{int((ft == pt).sum())}/{ft.size}",
            "promoted_hot_blocks": paged.promoted,
            "flat_s": flat.seconds, "paged_s": paged.seconds}


def phase_c(devices: int = 4, accesses: int | None = None,
            intervals: int = 2) -> dict:
    """The sharded sweep on a `devices`-chip mesh vs the one-chip vmap."""
    from repro.engine import fleet, simloop
    from repro.launch.mesh import make_fleet_mesh

    plan = fleet.SweepPlan.grid(
        policies=FLEET_POLICIES, seeds=FLEET_SEEDS, scenario=FLEET_SCENARIOS,
        intervals=intervals, accesses=accesses)
    groups = fleet.plan_groups(plan)

    def one_chip(group):
        def run():
            state0 = simloop.engine_init(group.spec)
            states = jax.tree.map(
                lambda x: np.broadcast_to(x, (len(group.cells),) + x.shape),
                state0)
            seeds = np.asarray([c.seed for c in group.cells], np.int32)
            finals, stats = simloop.engine_run_fused_batch(
                group.spec, states, seeds, group.intervals)
            return fleet.group_metrics(group, finals.sim.counters, stats)
        return run

    calls = {i: one_chip(g) for i, g in enumerate(groups)}
    calls["fleet"] = lambda: fleet.FleetRunner(
        mesh=make_fleet_mesh(devices)).run(plan)
    res = _parallel(calls)
    sharded = res.pop("fleet")
    for ref in res.values():
        for cell, want in ref.items():
            if sharded[cell] != want:
                raise AssertionError(
                    f"{cell.label}: sharded {sharded[cell]} != one-chip {want}")
    return {"devices": devices, "cells": len(sharded), "groups": len(groups),
            "cells_per_group": len(FLEET_SEEDS),
            "sharded_eq_one_chip": "bitwise, row for row"}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs the sharded sweep (phase C) and nothing else")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}; "
              "there is no fallback", file=sys.stderr)
        return 1
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(jax.devices())} device(s)", file=sys.stderr)
        return 1

    from repro.configs import get_config
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    clock = CompileClock()
    if args.chips == 4:
        clock.phase("C", phase_c, devices=4)
    else:
        clock.phase("A", phase_a)
        clock.phase("B", phase_b, get_config(SERVE_ARCH))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
