"""Multi-process fleet bootstrap: jax.distributed bring-up + worker spawning.

The fleet axis (engine.fleet) is embarrassingly parallel, so scaling a sweep
past one host is "just" a bigger 1-D mesh — the hard part is process bring-up
and getting results back:

  spawn(...)       subprocess-launches N copies of a worker command on THIS
                   host, each with REPRO_DIST_* env vars + a forced CPU device
                   count (--xla_force_host_platform_device_count), emulating an
                   N-host fleet for tests/CI. On real TPU pods the launcher is
                   the cluster scheduler and spawn() is not needed.
  initialize(...)  called by every worker (directly or via
                   launch.mesh.make_fleet_mesh(processes=N)): reads the worker
                   env, forces the local device count BEFORE jax touches its
                   backends, enables gloo cross-process CPU collectives, and
                   calls jax.distributed.initialize. Idempotent; a no-op
                   single-process run when no worker env is present.
  barrier/kv_*     thin wrappers over the jax coordination service used to
                   sequence workers and ship small host-side blobs (e.g.
                   verification rows) to the coordinator without touching the
                   filesystem.

jax.distributed can only be initialized ONCE per process (re-init raises), so
tests exercise this module through subprocesses — see docs/fleet.md for the
troubleshooting notes.

`python -m repro.launch.distributed --processes 2 --local-devices 2 --check`
is the self-contained smoke: the launcher runs a small single-device reference
sweep, spawns the workers (each re-runs this module with worker env set), and
asserts the multi-process FleetResult is bit-identical — the ci.sh
distributed leg and tests/test_fleet_distributed.py both drive it.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import pathlib
import socket
import subprocess
import sys
import tempfile
import threading
import time

ENV_COORDINATOR = "REPRO_DIST_COORDINATOR"
ENV_NUM_PROCESSES = "REPRO_DIST_NUM_PROCESSES"
ENV_PROCESS_ID = "REPRO_DIST_PROCESS_ID"
ENV_LOCAL_DEVICES = "REPRO_DIST_LOCAL_DEVICES"

_DEVICE_COUNT_FLAG = "--xla_force_host_platform_device_count"


@dataclasses.dataclass(frozen=True)
class WorkerEnv:
    """One worker's slot in the process fleet (parsed from REPRO_DIST_*)."""

    coordinator: str
    num_processes: int
    process_id: int
    local_devices: int | None = None

    def environ(self) -> dict[str, str]:
        env = {
            ENV_COORDINATOR: self.coordinator,
            ENV_NUM_PROCESSES: str(self.num_processes),
            ENV_PROCESS_ID: str(self.process_id),
        }
        if self.local_devices is not None:
            env[ENV_LOCAL_DEVICES] = str(self.local_devices)
        return env


def worker_env() -> WorkerEnv | None:
    """The WorkerEnv of this process, or None outside a spawned fleet."""
    if ENV_COORDINATOR not in os.environ:
        return None
    local = os.environ.get(ENV_LOCAL_DEVICES)
    return WorkerEnv(
        coordinator=os.environ[ENV_COORDINATOR],
        num_processes=int(os.environ[ENV_NUM_PROCESSES]),
        process_id=int(os.environ[ENV_PROCESS_ID]),
        local_devices=int(local) if local else None,
    )


def free_port() -> int:
    """An OS-assigned free TCP port for the coordination service."""
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _force_local_devices(n: int) -> None:
    """Force the host-platform device count; must run before backend init."""
    flags = os.environ.get("XLA_FLAGS", "")
    if _DEVICE_COUNT_FLAG in flags:
        return  # the caller already pinned a count; respect it
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        raise RuntimeError(
            "distributed.initialize: jax backends are already initialized, "
            f"too late to force {n} local CPU devices — call initialize() "
            "(or make_fleet_mesh(processes=N)) before any jax.devices()/jit"
        )
    os.environ["XLA_FLAGS"] = f"{flags} {_DEVICE_COUNT_FLAG}={n}".strip()


def is_initialized() -> bool:
    from jax._src import distributed as jdist

    return jdist.global_state.client is not None


def initialize(
    env: WorkerEnv | None = None,
    *,
    collectives: str = "gloo",
    cluster_detect: bool = False,
) -> bool:
    """Bring up jax.distributed for this process; returns True if distributed.

    Reads the spawn() worker env when `env` is None; without one this is a
    single-process no-op (the zero-config path every test and CLI run takes)
    unless `cluster_detect=True`, which lets jax auto-detect a real cluster
    (TPU pods, SLURM, ...) from its own environment instead. Safe to call
    more than once — re-init of an already-connected process is skipped. CPU
    cross-process collectives (the retire path's all-gather) need gloo,
    which must be selected before the backends exist.
    """
    env = env or worker_env()
    if is_initialized():
        return True
    if env is None and not cluster_detect:
        return False
    import jax

    from jax._src import xla_bridge

    if env is not None and env.local_devices:
        _force_local_devices(env.local_devices)
    set_collectives = collectives and not xla_bridge.backends_are_initialized()
    if set_collectives:
        prev = jax.config.jax_cpu_collectives_implementation
        jax.config.update("jax_cpu_collectives_implementation", collectives)
    try:
        if env is None:
            jax.distributed.initialize()  # cluster auto-detection
        else:
            jax.distributed.initialize(
                coordinator_address=env.coordinator,
                num_processes=env.num_processes,
                process_id=env.process_id,
            )
    except Exception:
        if set_collectives:
            # gloo without a coordination service poisons CPU backend
            # bring-up; restore so a failed probe leaves jax usable
            jax.config.update("jax_cpu_collectives_implementation", prev)
        raise
    return True


def ensure_initialized(processes: int) -> None:
    """make_fleet_mesh(processes=N)'s contract: N connected jax processes.

    Bring-up order: an already-connected process is a no-op; a spawn() worker
    env wins; otherwise jax's own cluster auto-detection is attempted — the
    real-host path, where the cluster scheduler launched the processes and
    no REPRO_DIST_* env exists.
    """
    if processes <= 1:
        return
    detect_err = None
    try:
        initialize(cluster_detect=worker_env() is None)
    except Exception as e:  # no spawn env and no detectable cluster
        detect_err = e
    import jax

    if jax.process_count() != processes:
        hint = (
            "spawn this program through launch.distributed.spawn (or set the "
            f"{ENV_COORDINATOR}/{ENV_NUM_PROCESSES}/{ENV_PROCESS_ID} worker "
            "env) so every process joins the coordination service; on real "
            "clusters, launch one process per host and jax auto-detection "
            "finds the coordinator"
        )
        raise RuntimeError(
            f"make_fleet_mesh(processes={processes}): jax sees "
            f"{jax.process_count()} process(es) — {hint}"
        ) from detect_err


# -- coordination-service helpers (barrier + tiny-blob KV) -------------------


def _client():
    from jax._src import distributed as jdist

    client = jdist.global_state.client
    if client is None:
        raise RuntimeError(
            "distributed coordination service not initialized — "
            "call launch.distributed.initialize() first"
        )
    return client


def barrier(name: str, timeout_s: int = 120) -> None:
    """Block until every process reaches `name` (coordination service)."""
    _client().wait_at_barrier(name, timeout_in_ms=timeout_s * 1000)


def kv_put(key: str, data: bytes) -> None:
    """Publish a small host-side blob to the coordination service KV store."""
    _client().key_value_set_bytes(key, data)


def kv_get(key: str, timeout_s: int = 120) -> bytes:
    """Blocking fetch of a KV blob (e.g. the coordinator collecting shards)."""
    return _client().blocking_key_value_get_bytes(key, timeout_s * 1000)


# -- local process-fleet spawning (CPU emulation of a multi-host fleet) ------


def spawn(
    argv: list[str],
    processes: int,
    *,
    local_devices: int | None = None,
    coordinator: str | None = None,
    env: dict[str, str] | None = None,
    timeout_s: int = 600,
) -> list[subprocess.CompletedProcess]:
    """Run `argv` as an N-process jax fleet on this host; wait for all.

    Every worker gets the same argv plus its REPRO_DIST_* slot; worker code
    calls initialize() (or make_fleet_mesh(processes=N)) to join. Raises on
    the first nonzero exit, with that worker's tail of stderr.
    """
    coordinator = coordinator or f"127.0.0.1:{free_port()}"
    procs = []
    for pid in range(processes):
        wenv = WorkerEnv(coordinator, processes, pid, local_devices)
        penv = {**os.environ, **(env or {}), **wenv.environ()}
        procs.append(subprocess.Popen(
            argv, env=penv, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True,
        ))
    # Drain every worker's pipes CONCURRENTLY: a chatty worker that fills its
    # OS pipe buffer would otherwise block mid-collective, stalling the whole
    # fleet until the sequential reader reached it (or the timeout fired).
    results: list = [None] * processes
    def drain(pid: int, p: subprocess.Popen) -> None:
        out, err = p.communicate()
        results[pid] = subprocess.CompletedProcess(argv, p.returncode, out, err)

    threads = [
        threading.Thread(target=drain, args=(pid, p), daemon=True)
        for pid, p in enumerate(procs)
    ]
    deadline = time.monotonic() + timeout_s
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=max(deadline - time.monotonic(), 0.0))
        if any(t.is_alive() for t in threads):
            raise RuntimeError(
                f"distributed fleet timed out after {timeout_s}s "
                f"({sum(t.is_alive() for t in threads)}/{processes} workers "
                "still running)"
            )
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for t in threads:
            t.join(timeout=10)
    done = results
    for pid, r in enumerate(done):
        if r.returncode != 0:
            raise RuntimeError(
                f"distributed worker {pid}/{processes} exited "
                f"{r.returncode}:\n{r.stderr[-4000:]}"
            )
    return done


# -- CLI: spawn-and-verify smoke ---------------------------------------------

#: The smoke plan: 2 compile signatures (streamcluster vs soplex shapes) and
#: group sizes (3, 2) that divide NO even mesh — every leg exercises padding.
_SMOKE = dict(intervals=2, accesses=2000)


def _smoke_plan():
    from repro.engine import fleet

    return fleet.SweepPlan.grid(
        ["streamcluster"], ["rainbow"], (0, 1, 2), **_SMOKE
    ) + fleet.SweepPlan.grid(["soplex"], ["rainbow"], (0, 1), **_SMOKE)


def _result_rows(res) -> list[dict]:
    return [
        {"label": c.label, "seed": c.seed, **{
            f: getattr(m, f)
            for f in ("ipc", "mpki", "migrations", "total_cycles", "mig_bytes")
        }}
        for c, m in res.items()
    ]


def _worker_main(args, wenv: WorkerEnv) -> list[dict]:
    """SPMD body every spawned process runs: sweep the smoke plan, stream it,
    and cross-check every process finalized the SAME rows (KV store)."""
    initialize(wenv)
    import jax

    from repro.engine import fleet
    from repro.launch.mesh import make_fleet_mesh

    mesh = make_fleet_mesh(processes=wenv.num_processes)
    spans = {d.process_index for d in mesh.devices.flat}
    assert len(spans) == wenv.num_processes, (
        f"fleet mesh spans processes {spans}, expected {wenv.num_processes}"
    )
    runner = fleet.FleetRunner(mesh=mesh)
    plan = _smoke_plan()
    res = runner.run(plan)
    streamed = dict(runner.run_iter(plan))
    assert {c: streamed[c] for c in res} == dict(res.items()), (
        "streamed run_iter diverged from barrier run"
    )
    # the prefetch pipeline must surface per-group timings on EVERY process
    assert len(runner.timings) == 2 and all(
        t.cells >= 1 and t.scan_s >= 0 for t in runner.timings
    ), f"per-group timings missing in the fleet: {runner.timings}"
    rows = _result_rows(res)
    # the retire all-gather promises every process the same bytes — verify it
    # for real: workers publish their rows, the coordinator compares.
    me = jax.process_index()
    if me != 0:
        kv_put(f"smoke/rows/{me}", json.dumps(rows).encode())
    else:
        for peer in range(1, wenv.num_processes):
            peer_rows = json.loads(kv_get(f"smoke/rows/{peer}"))
            assert peer_rows == rows, (
                f"process {peer} finalized different rows than process 0"
            )

    # journal leg: a multi-process sweep checkpoints (process 0 writes), then
    # a second run replays PURELY from the journal — workers adopt process
    # 0's synced view, so this exercises the cross-process resume path too.
    journal = pathlib.Path(tempfile.gettempdir()) / (
        f"repro-fleet-smoke-{wenv.coordinator.rsplit(':', 1)[-1]}.jsonl"
    )
    if me == 0 and journal.exists():
        journal.unlink()
    barrier("smoke/journal-clean")
    try:
        # batched retirement (flush_groups=2): both groups coalesce into one
        # write; the generator-finalize flush makes them durable for replay
        first = runner.run(
            plan, journal=fleet.FleetJournal(journal, flush_groups=2)
        )
        replay = runner.run(plan, journal=journal)
        assert dict(first.items()) == dict(res.items()), (
            "journaled sweep diverged from barrier run"
        )
        assert dict(replay.items()) == dict(res.items()), (
            "journal replay diverged from barrier run"
        )
    finally:
        barrier("smoke/journal-done")
        if me == 0 and journal.exists():
            journal.unlink()
    return rows


def _launcher_main(args) -> int:
    # the spawn path IS the CPU emulation mode (forced host devices only
    # exist on the CPU platform) — pin it for the workers and the oracle
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    port = free_port()
    coordinator = f"127.0.0.1:{port}"
    argv = [sys.executable, "-m", "repro.launch.distributed"]
    reference = None
    if args.check:
        # single-device oracle BEFORE spawning: this process never joins the
        # fleet, so its jax state is independent of the workers'.
        from repro.engine import fleet

        reference = _result_rows(fleet.FleetRunner().run(_smoke_plan()))
    results = spawn(
        argv, args.processes,
        local_devices=args.local_devices, coordinator=coordinator,
        timeout_s=args.timeout,
    )
    rows = None
    for r in results:
        for line in r.stdout.splitlines():
            if line.startswith("SMOKE_ROWS "):
                rows = json.loads(line[len("SMOKE_ROWS "):])
    if rows is None:
        raise RuntimeError("no SMOKE_ROWS line in worker stdout")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rows, f)
    if args.check:
        if rows != reference:
            print("MISMATCH\n single-device:", reference, "\n fleet:", rows)
            return 1
        print(
            f"distributed smoke OK: {args.processes} processes x "
            f"{args.local_devices or 'native'} devices, "
            f"{len(rows)} cells bit-identical to single-device"
        )
    return 0


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--processes", type=int, default=2)
    ap.add_argument("--local-devices", type=int, default=2,
                    help="forced CPU devices per worker (emulated hosts)")
    ap.add_argument("--check", action="store_true",
                    help="compare the fleet result to a single-device oracle")
    ap.add_argument("--out", default=None, help="write result rows JSON here")
    ap.add_argument("--timeout", type=int, default=600)
    args = ap.parse_args(argv)

    wenv = worker_env()
    if wenv is not None:  # spawned copy: run the SPMD worker body
        rows = _worker_main(args, wenv)
        if wenv.process_id == 0:
            print("SMOKE_ROWS " + json.dumps(rows), flush=True)
        return 0
    return _launcher_main(args)


if __name__ == "__main__":
    sys.exit(main())
