#!/usr/bin/env bash
# Tier-1 verify + engine smoke, reproducible from a clean checkout:
#   pip install -r requirements.txt && bash scripts/ci.sh
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "== tier-1: unit + system tests =="
python -m pytest -x -q

echo "== engine smoke: 2-interval scanned sim (rainbow + flat-static) =="
python - <<'EOF'
from repro.sim.runner import simulate

for policy in ("rainbow", "flat-static"):
    m = simulate("streamcluster", policy, intervals=2, accesses=4000)
    assert m.ipc > 0 and m.total_cycles > 0, (policy, m)
    print(f"  {policy:12s} ipc={m.ipc:.4f} mpki={m.mpki:.4f} "
          f"migrations={m.migrations}")
print("engine smoke OK")
EOF

echo "== multi-device smoke: sharded FleetRunner on 4 forced host devices =="
XLA_FLAGS="--xla_force_host_platform_device_count=4" python - <<'EOF'
import jax
from repro.engine import fleet
from repro.sim.runner import simulate

assert len(jax.devices()) == 4, jax.devices()
plan = fleet.SweepPlan.grid(
    ["streamcluster"], ["rainbow", "flat-static"], (0, 1, 2),
    intervals=2, accesses=3000,
)  # 6 cells -> 2 groups of 3, each padded to the 4-device mesh
res = fleet.FleetRunner().run(plan)
assert len(res) == 6
one = simulate("streamcluster", "rainbow", intervals=2, accesses=3000, seed=2)
got = res[("streamcluster", "rainbow", 2)]
assert got.ipc == one.ipc and got.migrations == one.migrations, (got, one)
print(f"  sharded fleet: {len(res)} cells across {len(jax.devices())} devices, "
      "bit-identical to single-device engine")
EOF

echo "== scenario smoke: fused in-scan generation vs staged oracle on a 4-device fleet =="
XLA_FLAGS="--xla_force_host_platform_device_count=4" python - <<'EOF'
import jax
from repro.engine import fleet
from repro.sim.runner import simulate

assert len(jax.devices()) == 4, jax.devices()
plan = fleet.SweepPlan.grid(
    policies=["rainbow", "flat-static"], seeds=(0, 1, 2),
    scenario=["stress/zipf-hotspot", "stress/seq-scan"],
    intervals=2, accesses=3000,
)  # 4 fused groups of 3 cells each, all padded to the 4-device mesh
res = fleet.FleetRunner().run(plan)
assert len(res) == 12
for name in ("stress/zipf-hotspot", "stress/seq-scan"):
    fused = res.one(app=name, policy="rainbow", seed=2)
    staged = simulate(name, "rainbow", intervals=2, accesses=3000, seed=2)
    assert fused.ipc == staged.ipc and fused.migrations == staged.migrations, (
        name, fused, staged)
    assert fused.total_cycles == staged.total_cycles
print(f"  scenario fleet: {len(res)} fused cells across "
      f"{len(jax.devices())} devices, bit-identical to the staged oracle")
EOF

echo "== distributed smoke: 2-process x 2-device fleet vs single-device oracle =="
# Gated on platform: the spawned workers force CPU host devices, which only
# emulates a multi-host fleet when this host itself runs the CPU backend.
if python -c "import jax; raise SystemExit(0 if jax.default_backend() == 'cpu' else 1)"; then
    python -m repro.launch.distributed --processes 2 --local-devices 2 --check
else
    echo "  skipped (non-CPU backend: real hosts join via jax.distributed, not spawn)"
fi

echo "== streamed sweep: run_iter + journal resume bit-identical to barrier run =="
python - <<'EOF'
import pathlib
import tempfile

from repro.engine import fleet
from repro.launch.distributed import _smoke_plan

plan = _smoke_plan()  # 2 compile signatures, group sizes (3, 2): always padded
runner = fleet.FleetRunner()
barrier = runner.run(plan)
assert dict(runner.run_iter(plan)) == dict(barrier.items()), "stream != barrier"
with tempfile.TemporaryDirectory() as td:
    journal = pathlib.Path(td) / "sweep.jsonl"
    it = runner.run_iter(plan, journal=journal)
    for _ in range(3):
        next(it)  # retire only the first group, then abandon the sweep
    it.close()
    resumed = runner.run(plan, journal=journal)
    assert dict(resumed.items()) == dict(barrier.items()), "resume != barrier"
print(f"  streamed + resumed: {len(barrier)} cells bit-identical to barrier run")
EOF

echo "== atlas smoke: policy atlas 2x2x2, streamed + journaled + resume-checked =="
ATLAS_TMP="$(mktemp -d)"
trap 'rm -rf "$ATLAS_TMP"' EXIT
python -m benchmarks.policy_atlas \
    --scenarios 2 --policies 2 --seeds 2 \
    --journal "$ATLAS_TMP/atlas.jsonl" --out "$ATLAS_TMP/BENCH_atlas.json" \
    --resume-check
python - "$ATLAS_TMP/BENCH_atlas.json" <<'EOF'
import json, sys

atlas = json.load(open(sys.argv[1]))
assert atlas["cells"] == 8 and atlas["winners"], atlas["config"]
assert len(atlas["timings"]) == len(atlas["journal_timings"]) == 4
print(f"  atlas smoke: {atlas['cells']} cells, winners={atlas['winners']}")
EOF

echo "== autotune smoke: tuned ControlPolicy beats the default on a recorded trace =="
python - <<'EOF'
import jax
from repro.configs import get_reduced_config
from repro.engine.autotune import TunePlan, autotune, evaluate
from repro.memory.kvcache import PagedConfig
from repro.models import model as M
from repro.serving.rainbow_decode import record_mass_trace

cfg = get_reduced_config("qwen3-4b")
key = jax.random.PRNGKey(0)
B, S = 2, 16
pcfg = PagedConfig(block_size=4, blocks_per_seq=S // 4, hot_slots=4,
                   top_n=4, max_promotions=4, interval_steps=8)
prompt = jax.random.randint(key, (B, 8), 0, cfg.vocab_size)
params = M.init_params(cfg, key, tp=1)
trace, _ = record_mass_trace(cfg, pcfg, params, prompt, steps=S)

plan = TunePlan.grid(pcfg.policy, interval_steps=(2, 8))  # 2 candidates
res = autotune(plan, trace)
assert res.improved, f"tuned must beat default: {res.summary()}"
cands = plan.candidates()
assert evaluate(trace, cands, runner="vmap") == evaluate(
    trace, cands, runner="sharded"), "vmap vs sharded evaluation diverged"
print(f"  {res.summary()}")
print("autotune smoke OK")
EOF

echo "== engine throughput smoke: hot-path gate (fastpath >= 1.4x, bit-identical) + BENCH_engine.json schema =="
python -m benchmarks.engine_throughput
python - <<'EOF'
import json

from benchmarks.engine_throughput import GATE_FLOOR, GATE_POLICIES, POLICY

bench = json.load(open("BENCH_engine.json"))
for key in ("benchmark", "quick", "unit", "rows", "headline",
            "scanned_vs_host_speedup", "profile", "gate"):
    assert key in bench, f"BENCH_engine.json missing {key!r}"
assert bench["unit"] == "accesses_per_sec"
gate = bench["gate"]
assert gate["floor"] == GATE_FLOOR and gate["bit_identical"] is True
assert gate["speedup"] >= GATE_FLOOR, (
    f"hot-path gate below floor in BENCH_engine.json: {gate['speedup']}")
assert set(gate["per_policy"]) == set(GATE_POLICIES)
for leg in gate["per_policy"].values():
    assert {"reference_s", "fast_s", "speedup", "accesses_per_sec"} <= set(leg)
phases = bench["profile"]["phases"]
assert {"tlb", "observe", "plan", "apply"} <= set(phases), sorted(phases)
for p in phases.values():
    assert {"wall_s", "compile_s", "calls", "flops", "bytes_accessed"} <= set(p)
print(f"  engine gate: {POLICY} fastpath {gate['speedup']:.2f}x reference "
      f"(floor {GATE_FLOOR}x), profile phases: {sorted(phases)}")
EOF

echo "== timing smoke: flat == queueing-with-infinite-banks (bitwise) + contention sanity =="
python - <<'EOF'
import dataclasses

from repro.sim.runner import simulate
from repro.timing import QueueGeometry

for policy in ("rainbow", "hscc-4kb-mig"):
    flat = simulate("streamcluster", policy, intervals=2, accesses=4000)
    inf = simulate("streamcluster", policy, intervals=2, accesses=4000,
                   timing_model="queueing",
                   queue_geometry=QueueGeometry.flat_floor())
    assert dataclasses.asdict(flat) == dataclasses.asdict(inf), (
        f"{policy}: flat != queueing-with-infinite-banks (bitwise)")
    tight = simulate("streamcluster", policy, intervals=2, accesses=4000,
                     timing_model="queueing",
                     queue_geometry=QueueGeometry(1, 2, 1, 2))
    assert tight.bank_stall_cycles > 0, policy
    assert tight.total_cycles > flat.total_cycles, policy
    print(f"  {policy:12s} flat-floor bitwise OK, constrained "
          f"bank_stall={tight.bank_stall_cycles:.3e}")
print("timing smoke OK")
EOF

echo "== timing contention: bank-geometry x policy sweep + BENCH_timing.json schema =="
python -m benchmarks.timing_contention
python - <<'EOF'
import json

bench = json.load(open("BENCH_timing.json"))
for key in ("benchmark", "quick", "headline", "rows", "flat_floor_bitwise",
            "gap_ipc_flat", "gap_ipc_constrained", "gate"):
    assert key in bench, f"BENCH_timing.json missing {key!r}"
assert bench["flat_floor_bitwise"] is True, "flat-floor invariant broken"
gate = bench["gate"]
assert {"floor", "speedup"} <= set(gate)
assert gate["speedup"] >= gate["floor"], (
    f"policy-gap shift below floor: {gate['speedup']} < {gate['floor']}")
for row in bench["rows"]:
    assert {"geometry", "app", "policy", "ipc", "total_cycles",
            "bank_stall_cycles", "mig_stall_cycles", "queue_occ_dram",
            "queue_occ_nvm"} <= set(row), row
print(f"  timing gate: {bench['headline']}")
EOF

echo "== nomad smoke: async family vs rainbow, staged == fused bitwise + BENCH_nomad.json schema =="
python - <<'EOF'
import dataclasses

from repro.sim.runner import simulate
from repro.timing import get_geometry

kw = dict(intervals=3, accesses=4000, seed=3, timing_model="queueing",
          queue_geometry=get_geometry("constrained"))
staged = simulate("stress/zipf-hotspot", "nomad", **kw)
fused = simulate("stress/zipf-hotspot", "nomad", fused=True, **kw)
assert dataclasses.asdict(staged) == dataclasses.asdict(fused), (
    "nomad: staged != fused (bitwise)")
rainbow = simulate("stress/zipf-hotspot", "rainbow", **kw)
assert staged.migrations > 0 and staged.mig_aborts > 0, staged
assert rainbow.mig_aborts == 0, rainbow
print(f"  nomad staged==fused bitwise OK: {staged.migrations} migrations, "
      f"{staged.mig_aborts} aborts (rainbow mig_stall="
      f"{rainbow.mig_stall_cycles:.3e}, nomad={staged.mig_stall_cycles:.3e})")
EOF
python -m benchmarks.nomad_async
python - <<'EOF'
import json

bench = json.load(open("BENCH_nomad.json"))
for key in ("benchmark", "quick", "headline", "rows",
            "sync_degenerate_bitwise", "mig_stall_relief", "total_aborts",
            "gate"):
    assert key in bench, f"BENCH_nomad.json missing {key!r}"
assert bench["sync_degenerate_bitwise"] is True, (
    "async_window=1 must be bit-identical to synchronous rainbow")
gate = bench["gate"]
assert {"floor", "speedup"} <= set(gate)
assert gate["speedup"] >= gate["floor"], (
    f"mig_stall relief below floor: {gate['speedup']} < {gate['floor']}")
assert bench["total_aborts"] > 0, "abort path never exercised"
for row in bench["rows"]:
    assert {"geometry", "app", "policy", "ipc", "total_cycles", "migrations",
            "mig_aborts", "bank_stall_cycles", "mig_stall_cycles"} <= set(row), row
print(f"  nomad gate: {bench['headline']}")
EOF

echo "== hscc parity: STREAMED fleet vs recorded snapshot (spot check, rel-err 0.0) =="
python scripts/validate_hscc_parity.py --stream --apps soplex
echo "  (full table: scripts/validate_hscc_parity.py [--stream])"

echo "== bench aggregate: every BENCH_*.json gate must pass (non-zero exit on failure) =="
python -m benchmarks.run --aggregate-only
