"""Sharded FleetRunner: mesh-parallel (app x policy x seed x config) sweeps.

The paper's evaluation (§V, Figs. 7-15) is a grid of (workload x policy x
machine-config) simulations. PR 1 fused ONE simulation into a single lax.scan
and vmapped the seed fleet; this module owns the grid itself:

  SweepPlan    declares the cells (apps x policies x seeds x MachineConfig
               overrides, each optionally tagged for later slicing);
  FleetRunner  groups cells that share a compile signature (EngineSpec +
               interval shape), pads each group's flattened fleet axis to the
               mesh size, shards it across a 1-D "fleet" device mesh via
               shard_map of the SAME vmapped body engine_run_batch jits
               (launch.mesh.make_fleet_mesh / launch.sharding.batch_shardings),
               and pipelines host-side staging against the in-flight device
               scans: a background prepare thread generates traces, stages
               them sharded, and resolves each group's compiled executable
               (CompileCache: AOT executables keyed by the compile-signature
               digest, backed by jax's persistent compilation cache so
               resumed/repeated processes skip XLA entirely) up to
               `prefetch_depth` groups ahead of retirement, recycling pooled
               host staging buffers instead of reallocating per group
               (fleet-state buffers are donated, so device memory is bounded
               by the staged depth); `pipeline=False` preserves the
               pre-pipeline inline double-buffered path as the differential
               reference;
  FleetResult  maps every cell back to its SimMetrics, in plan order, with
               tag/field selection for figure scripts.

The mesh may span MULTIPLE jax processes (launch.mesh.make_fleet_mesh
(processes=N) / launch.distributed): staging then feeds each process's
addressable shards via make_array_from_callback and retire all-gathers each
group's (tiny) stats to every process, so the SPMD result is bit-identical
to the single-device path. `run_iter` streams (cell, metrics) pairs as each
group retires — reusing the same prefetch pipeline — and an optional
FleetJournal checkpoints retired groups (appends coalesced up to a watermark,
one fsync per flush) so a killed sweep resumes from the last *flushed* group
(docs/fleet.md). Per-group wall-clock timings (stage / compile / scan /
retire) land on `FleetRunner.timings` and in the journal records, so atlas
throughput regressions are attributable without a profiler.

One engine path from a single-CPU test to a multi-process parameter study:
every paper_fig* module, sim.runner.sweep, sensitivity sweeps, and future
autotuning searches declare a plan and render rows from the result.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import hashlib
import itertools
import json
import os
import pathlib
import queue
import threading
import time
from typing import Any, Iterator, Mapping

import jax
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

import repro.engine.simloop as simloop
from repro.engine.policy import (
    SIM_POLICY_PRESETS,
    ControlPolicy,
    resolve_policy,
)
from repro.launch.mesh import make_fleet_mesh
from repro.launch.sharding import batch_shardings
from repro.sim import trace as trace_mod
from repro.sim.config import MachineConfig
from repro.sim.runner import SimMetrics, finalize_metrics, totals_from_stats
from repro.utils.compile_cache import enable_compile_cache

Tags = tuple[tuple[str, Any], ...]


@dataclasses.dataclass(frozen=True)
class SweepCell:
    """One simulation of the sweep grid (hashable: it IS the result key).

    `control` overrides the controller knobs of the stateful policies with a
    ControlPolicy (the unified surface of engine.policy) — sweeps over
    (interval_steps, top_n, threshold_init, ...) declare policies natively
    instead of patching raw MachineConfig dicts.

    `app` may also name a registered scenario (repro.workloads.scenarios);
    `fused=True` then synthesizes its trace INSIDE the engine scan — no
    make_chunks_np staging at all — while `fused=False` materializes the
    same generator stream host-side (the staged differential oracle). A
    fused cell whose app is not a registered scenario fails loudly in
    plan_groups; there is no silent fallback to staged mode.
    """

    app: str
    policy: str
    seed: int = 7
    mc: MachineConfig = dataclasses.field(default_factory=MachineConfig)
    intervals: int = 5
    accesses: int | None = None
    counter_backend: str = "jax"
    control: ControlPolicy | None = None
    fused: bool = False
    tags: Tags = ()
    timing_model: str = "flat"
    queue_geometry: Any = None  # repro.timing.QueueGeometry | None

    @property
    def tag(self) -> dict[str, Any]:
        return dict(self.tags)

    @property
    def label(self) -> str:
        return f"{self.app}/{self.policy}/seed={self.seed}"

    def key(self) -> str:
        """The journal key: the human label + a digest of EVERY cell field.

        Two cells can share a label but differ in mc/intervals/control (e.g.
        sensitivity sweeps), so resume matches on the full identity — a
        journal recorded at one config can never satisfy another.
        """
        blob = repr((self.app, self.policy, self.seed, self.mc,
                     self.intervals, self.accesses, self.counter_backend,
                     self.control, self.fused, self.tags,
                     self.timing_model, self.queue_geometry))
        return f"{self.label}#{hashlib.sha1(blob.encode()).hexdigest()[:10]}"


@dataclasses.dataclass(frozen=True)
class SweepPlan:
    """An ordered set of SweepCells; the declarative input of FleetRunner."""

    cells: tuple[SweepCell, ...]

    @staticmethod
    def grid(
        apps=(),
        policies=(),
        seeds=(7,),
        *,
        mc: MachineConfig | None = None,
        intervals: int = 5,
        accesses: int | None = None,
        counter_backend: str = "jax",
        policy: ControlPolicy | str | None = None,
        scenario=None,
        tags: Tags = (),
        timing_model: str = "flat",
        queue_geometry=None,
    ) -> "SweepPlan":
        """The dense (apps x policies x seeds) grid at one machine config.

        `policy` (a ControlPolicy or a registered preset name) overrides the
        stateful policies' controller knobs for every cell of the grid — the
        native way to sweep (interval_steps, top_n, threshold_init, ...)
        without patching MachineConfig. Because one override's knobs are in
        one policy kind's units (e.g. hscc-2mb hot_slots counts superpages),
        grids mixing several stateful kinds reject an override; declare one
        grid per kind and `+` them. The override's counter_backend is
        authoritative over the `counter_backend` argument.

        `scenario` (a name or sequence of names from
        repro.workloads.scenarios) adds FUSED cells: their traces are
        synthesized inside the engine scan, so the runner never stages
        make_chunks_np arrays for them. Scenario names passed through `apps`
        instead run STAGED (host-materialized from the same generator stream
        — the differential oracle); unregistered scenario names are rejected
        here, loudly.
        """
        mc = mc or MachineConfig()
        apps, policies, seeds = tuple(apps), tuple(policies), tuple(seeds)
        if isinstance(scenario, str):
            scenario = (scenario,)
        scenario = tuple(scenario or ())
        if scenario:
            from repro.workloads import scenarios as scen

            unknown = [s for s in scenario if not scen.is_scenario(s)]
            if unknown:
                raise ValueError(
                    f"SweepPlan.grid: unregistered scenario(s) {unknown}; "
                    f"registered: {scen.available_scenarios()}"
                )
        control = None
        if policy is not None:
            stateful = {p for p in policies if p in SIM_POLICY_PRESETS}
            if len(stateful) > 1:
                raise ValueError(
                    "SweepPlan.grid: one `policy` override cannot apply to "
                    f"multiple stateful policy kinds {sorted(stateful)} — "
                    "their knobs use different units (hscc-2mb counts "
                    "superpage slots, rainbow/hscc-4kb count 4KB pages); "
                    "declare one grid per kind and add the plans"
                )
            control = resolve_policy(policy, "sim-rainbow", mc=mc)
            if counter_backend not in ("jax", control.counter_backend):
                raise ValueError(
                    "SweepPlan.grid: conflicting counter_backend "
                    f"({counter_backend!r} argument vs "
                    f"{control.counter_backend!r} on the policy override) — "
                    "set it on the ControlPolicy"
                )
        workloads = [(a, False) for a in apps] + [(n, True) for n in scenario]
        if bool(workloads) != bool(policies) or (workloads and not seeds):
            raise ValueError(
                "SweepPlan.grid: a lopsided grid (workloads without "
                f"policies/seeds, or vice versa: apps={apps!r}, "
                f"scenario={scenario!r}, policies={policies!r}, "
                f"seeds={seeds!r}) would silently declare ZERO cells — "
                "pass every axis, or none for an explicitly empty plan"
            )
        return SweepPlan(tuple(
            SweepCell(a, p, s, mc, intervals, accesses, counter_backend,
                      control, fused, tuple(tags),
                      timing_model=timing_model,
                      queue_geometry=queue_geometry)
            for a, fused in workloads for p in policies for s in seeds
        ))

    def __add__(self, other: "SweepPlan") -> "SweepPlan":
        return SweepPlan(self.cells + other.cells)

    def __len__(self) -> int:
        return len(self.cells)

    def __iter__(self) -> Iterator[SweepCell]:
        return iter(self.cells)


@dataclasses.dataclass(frozen=True)
class FleetGroup:
    """Cells sharing one compile signature -> one sharded device program."""

    spec: simloop.EngineSpec
    intervals: int
    cells: tuple[SweepCell, ...]
    meta: dict


def plan_groups(plan: SweepPlan) -> list[FleetGroup]:
    """Group plan cells by compile signature, preserving first-seen order.

    Apps change array shapes (footprint/superpage counts) and configs change
    the EngineSpec, so only (seed x same-shape app) cells fuse into one fleet
    axis; the signature is probed from profile metadata without generating a
    single access (trace.probe_meta).
    """
    buckets: dict[tuple, list[SweepCell]] = collections.defaultdict(list)
    metas: dict[tuple, dict] = {}
    seen: set[SweepCell] = set()
    for cell in plan.cells:
        if cell in seen:  # exact duplicates collapse to one run
            continue
        seen.add(cell)
        if cell.fused:
            # fused cells compile against the registered generator program;
            # an unregistered name must fail HERE, not fall back to staging
            from repro.workloads import scenarios as scen

            if not scen.is_scenario(cell.app):
                raise ValueError(
                    f"plan_groups: cell {cell.label!r} requests fused "
                    f"generation but {cell.app!r} is not a registered "
                    f"scenario (registered: {scen.available_scenarios()}); "
                    "fused cells never silently fall back to staged mode"
                )
        meta = trace_mod.probe_meta(cell.app, cell.accesses)
        spec = simloop.EngineSpec(
            policy=cell.policy,
            mc=cell.mc,
            num_superpages=meta["num_superpages"],
            footprint_pages=meta["footprint_pages"],
            counter_backend=cell.counter_backend,
            control=cell.control,
            source=(
                simloop.TraceSource(cell.app, cell.accesses)
                if cell.fused else None
            ),
            timing_model=cell.timing_model,
            queue_geometry=cell.queue_geometry,
        )
        key = (spec, cell.intervals, meta["accesses_per_interval"],
               meta["inst_per_access"])
        buckets[key].append(cell)
        metas[key] = meta
    return [
        FleetGroup(spec=key[0], intervals=key[1], cells=tuple(cells),
                   meta=metas[key])
        for key, cells in buckets.items()
    ]


def _shard_fleet(body, mesh):
    """shard_map of a vmapped fleet body over the 1-D "fleet" mesh axis."""
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P("fleet"), P("fleet")), out_specs=(P("fleet"), P("fleet")),
        check_vma=False,  # cells are independent, no collectives: nothing to check
    )


@functools.lru_cache(maxsize=None)
def _sharded_fused_fn(spec: simloop.EngineSpec, intervals: int, mesh):
    """shard_map of the fused-generation engine body over the fleet mesh.

    Per-shard it is exactly engine_run_fused_batch's program
    (simloop.batch_run_fused): traces are synthesized inside each shard's
    scan, so the only staged inputs are the (tiny) seed vector and initial
    fleet states — nothing for the double buffer to generate host-side.
    """
    return jax.jit(_shard_fleet(simloop.batch_run_fused(spec, intervals), mesh),
                   donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def _sharded_fleet_fn(spec: simloop.EngineSpec, mesh):
    """shard_map of the shared vmapped engine body over the fleet mesh.

    Per-shard it is exactly engine_run_batch's program (simloop.batch_run), so
    sharded results are bit-identical to the single-device vmap path. The
    fleet states are donated (the final states alias them); trace chunks are
    inputs-only to the scan so XLA cannot alias them into any output — their
    buffers are instead recycled when the group retires and the host drops its
    reference, bounding double-buffer memory at two staged groups.
    """
    return jax.jit(_shard_fleet(simloop.batch_run(spec), mesh),
                   donate_argnums=(0,))


# ---------------------------------------------------------------------------
# Compile caching: skip retracing/re-XLA for repeated compile signatures
# ---------------------------------------------------------------------------

def group_signature(group: FleetGroup, fleet_size: int, mesh) -> str:
    """Digest of everything determining one group's compiled fleet program.

    The probe_meta dict (shapes), the EngineSpec (policy program + geometry +
    controller knobs), interval count, the PADDED fleet size (monitor state
    shapes and the shard extent depend on it), and the mesh devices. Two
    groups with equal signatures are guaranteed to lower to the same program,
    so one AOT executable serves both.
    """
    blob = repr((group.spec, group.intervals, sorted(group.meta.items()),
                 int(fleet_size), tuple(str(d) for d in mesh.devices.flat)))
    return hashlib.sha1(blob.encode()).hexdigest()[:16]


class CompileCache:
    """AOT-compiled sharded fleet executables, keyed by group_signature.

    The pipelined runner lowers each group's program against the exact avals
    and shardings of its staged inputs and compiles it ahead of dispatch
    (jax.jit(...).lower(...).compile() — bit-identical to calling the jitted
    function, donation included). Repeated signatures across groups, plans,
    and runs of one process hit `_exes`; through the persistent compile
    cache (repro.utils.compile_cache), cache misses still skip the XLA
    backend work in any process that compiled the signature before.

    Thread-safe for the runner's single prepare thread + any number of
    readers; a module-level instance (COMPILE_CACHE) is shared by default so
    sequential FleetRunners reuse each other's compiles.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._exes: dict[str, Any] = {}
        self.hits = 0
        self.misses = 0
        self.compile_seconds = 0.0

    def stats(self) -> dict[str, Any]:
        with self._lock:
            return {"hits": self.hits, "misses": self.misses,
                    "entries": len(self._exes),
                    "compile_seconds": self.compile_seconds}

    def clear(self) -> None:
        with self._lock:
            self._exes.clear()
            self.hits = self.misses = 0
            self.compile_seconds = 0.0

    def get_or_compile(self, group: FleetGroup, staged, mesh):
        """(executable, signature, compile_seconds, cached) for one group.

        `staged` is the group's sharded (states, batch) — its avals are the
        lowering inputs, so an executable can only ever be reused where
        shapes, dtypes, AND shardings agree (group_signature covers them).
        """
        fleet_size = int(jax.tree.leaves(staged)[0].shape[0])
        sig = group_signature(group, fleet_size, mesh)
        with self._lock:
            exe = self._exes.get(sig)
            if exe is not None:
                self.hits += 1
                return exe, sig, 0.0, True
        t0 = time.perf_counter()
        if group.spec.source is not None:
            body = simloop.batch_run_fused(group.spec, group.intervals)
        else:
            body = simloop.batch_run(group.spec)
        jitted = jax.jit(_shard_fleet(body, mesh), donate_argnums=(0,))
        sds = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=x.sharding),
            staged,
        )
        exe = jitted.lower(*sds).compile()
        dt = time.perf_counter() - t0
        with self._lock:
            self.misses += 1
            self.compile_seconds += dt
            exe = self._exes.setdefault(sig, exe)
        return exe, sig, dt, False


def group_metrics(group: FleetGroup, counters, stats) -> dict[SweepCell, SimMetrics]:
    """Per-cell SimMetrics of one group's fleet outputs (final sim counters
    and per-interval stats, fleet axis leading); padding lanes are dropped."""
    stats_h = jax.tree.map(np.asarray, stats)
    counters_h = jax.tree.map(np.asarray, counters)
    out = {}
    for i, cell in enumerate(group.cells):
        per_cell = type(stats)(*(x[i] for x in stats_h))
        totals = totals_from_stats(
            cell.policy, cell.mc, per_cell, group.meta["accesses_per_interval"],
        )
        per_counters = type(counters)(*(x[i] for x in counters_h))
        out[cell] = finalize_metrics(
            cell.app, cell.policy, cell.mc, totals, per_counters,
            group.meta["inst_per_access"], group.meta["footprint_pages"],
        )
    return out


#: Process-wide default cache; pass `compile_cache=` to FleetRunner to isolate.
COMPILE_CACHE = CompileCache()


@dataclasses.dataclass(frozen=True)
class GroupTiming:
    """Wall-clock breakdown of one retired group (FleetRunner.timings).

    stage_s    host trace generation + sharded device transfer
    compile_s  trace/lower/XLA compile (0.0 on a CompileCache hit)
    scan_s     host wall blocked on the group's device results at retire —
               an upper bound on the un-overlapped scan time
    retire_s   stats gather + per-cell metric finalization (journal I/O is
               batched separately and excluded)
    """

    label: str
    signature: str
    cells: int
    stage_s: float
    compile_s: float
    scan_s: float
    retire_s: float
    compile_cached: bool

    def row(self) -> dict[str, Any]:
        return dataclasses.asdict(self)


class _StagingPool:
    """Recycled host staging buffers, keyed by padded batch geometry.

    Atlas-scale plans stage hundreds of groups with only a handful of
    distinct (fleet, intervals, accesses) geometries; reusing the padded
    TraceChunks buffers avoids reallocating (and re-faulting) hundreds of MB
    per group. A buffer is released back only after its group retires — by
    then the sharded scan has consumed the staged copy, so the next group may
    overwrite it even while earlier results are still being finalized.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._free: dict[tuple, list] = collections.defaultdict(list)
        self.allocated = 0
        self.reused = 0

    def acquire(self, key: tuple, alloc):
        with self._lock:
            free = self._free.get(key)
            if free:
                self.reused += 1
                return free.pop()
            self.allocated += 1
        return alloc()

    def release(self, key: tuple, bufs) -> None:
        with self._lock:
            self._free[key].append(bufs)


def _pad_fleet(arrs, pad: int):
    """Pad the leading fleet axis by repeating the last member `pad` times."""
    if pad == 0:
        return arrs
    return jax.tree.map(
        lambda x: np.concatenate([x, np.repeat(x[-1:], pad, axis=0)]), arrs
    )


def _mesh_is_multiprocess(mesh) -> bool:
    return len({d.process_index for d in mesh.devices.flat}) > 1


@functools.lru_cache(maxsize=None)
def _replicate_fn(mesh):
    """jit identity resharding fleet-sharded outputs to fully-replicated.

    The multi-process retire path: an all-gather over the fleet axis (gloo on
    CPU, native on TPU) makes every shard addressable on every process, so
    the per-group device_get and metric finalization stay SPMD-identical
    everywhere — each process sees the SAME bytes it would single-process.
    """
    return jax.jit(lambda t: t, out_shardings=NamedSharding(mesh, P()))


_journal_sync_ids = itertools.count()


def _sync_journal_view(recorded: dict[str, "SimMetrics"]):
    """Make process 0's journal view authoritative across a process fleet.

    Resume decisions must be SPMD-identical: if one process's filesystem view
    of the journal is stale (NFS attribute caches), it would stage groups its
    peers skip and the collectives would deadlock. Process 0 broadcasts its
    loaded records through the coordination-service KV store; everyone else
    adopts them verbatim (the KV key carries a per-call sequence number, and
    all processes call in the same order, so concurrent sweeps can't cross).
    """
    import jax

    from repro.launch import distributed

    key = f"fleet-journal/{next(_journal_sync_ids)}"
    if jax.process_index() == 0:
        distributed.kv_put(key, json.dumps(
            {k: dataclasses.asdict(m) for k, m in recorded.items()}
        ).encode())
        return recorded
    return {
        k: SimMetrics(**fields)
        for k, fields in json.loads(distributed.kv_get(key)).items()
    }


class FleetJournal:
    """Append-only JSONL checkpoint of retired groups (streamed sweeps).

    One header line, then one record per retired FleetGroup mapping each
    cell's `SweepCell.key()` to its SimMetrics fields (plus that group's
    GroupTiming, which load() ignores). Appends are COALESCED: records buffer
    in memory and hit the file — one write, one fsync — when `flush_groups`
    records or `flush_bytes` of JSON accumulate, on an explicit flush()/
    close(), or when the streaming generator finalizes (run_iter flushes in
    its `finally`, so even a close()d iterator persists what it retired).
    `flush_groups=1` restores the original fsync-per-group durability.

    A killed sweep loses at worst the unflushed buffer plus one torn tail
    line, which load() discards — resume re-runs those groups and appends to
    the same file. Only process 0 of a multi-process fleet writes; every
    process reads (the journal must live on a filesystem all workers share).
    """

    #: Journal schema version. v1 headers carried only the version number;
    #: v2 headers also record the SimMetrics field names (`schema`) so a
    #: resume against a journal written by a DIFFERENT build fails loudly at
    #: load() instead of deep inside SimMetrics(**fields) — or, worse,
    #: silently dropping fields the old build never wrote.
    VERSION = 2

    def __init__(self, path: str | os.PathLike, *, flush_groups: int = 8,
                 flush_bytes: int = 4 << 20):
        if flush_groups < 1:
            raise ValueError(
                f"FleetJournal: flush_groups must be >= 1 (got {flush_groups})"
            )
        self.path = pathlib.Path(path)
        self.flush_groups = flush_groups
        self.flush_bytes = flush_bytes
        self._buf: list[str] = []
        self._buf_bytes = 0

    @property
    def pending(self) -> int:
        """Buffered records not yet durable (0 right after a flush)."""
        return len(self._buf)

    def __enter__(self) -> "FleetJournal":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def load(self) -> dict[str, SimMetrics]:
        """Completed cells keyed by SweepCell.key(); {} for a fresh journal."""
        if not self.path.exists():
            return {}
        done: dict[str, SimMetrics] = {}
        known = {f.name for f in dataclasses.fields(SimMetrics)}
        saw_header = False
        with self.path.open() as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    break  # torn tail write from a kill; earlier lines stand
                if rec.get("kind") == "fleet-journal":
                    if rec.get("version") != self.VERSION:
                        raise ValueError(
                            f"{self.path}: journal version {rec.get('version')}"
                            f" != {self.VERSION}; re-run with a fresh "
                            "--journal path (mixed-version journals cannot "
                            "be resumed)"
                        )
                    unknown = set(rec.get("schema", ())) - known
                    if unknown:
                        raise ValueError(
                            f"{self.path}: journal records SimMetrics fields "
                            f"unknown to this build: {sorted(unknown)}; "
                            "re-run with a fresh --journal path"
                        )
                    saw_header = True
                    continue
                if not saw_header:
                    raise ValueError(
                        f"{self.path}: cell record before any fleet-journal "
                        "header — a headerless (pre-versioning) or truncated "
                        "journal; re-run with a fresh --journal path"
                    )
                for key, fields in rec["cells"].items():
                    done[key] = SimMetrics(**fields)
        return done

    def load_timings(self) -> list[dict]:
        """GroupTiming rows of every flushed group, in retirement order.

        The atlas trajectory artifact: where a resumed run's wall-clock went,
        across every process that ever appended to this journal.
        """
        if not self.path.exists():
            return []
        rows: list[dict] = []
        with self.path.open() as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    break
                if "timing" in rec:
                    rows.append(rec["timing"])
        return rows

    def _drop_torn_tail(self) -> bool:
        """Truncate a partial last line (kill mid-write) before appending.

        load() already ignores the torn line; without this, the next append
        would glue its record onto the fragment and corrupt it too. Returns
        whether the file still has content (i.e. whether a header exists).
        """
        if not self.path.exists():
            return False
        with self.path.open("rb+") as f:
            data = f.read()
            if data and not data.endswith(b"\n"):
                keep = data.rfind(b"\n") + 1
                f.truncate(keep)
                data = data[:keep]
            return bool(data)

    def append(self, cells: dict[SweepCell, SimMetrics],
               timing: GroupTiming | None = None) -> None:
        """Record one retired group (coordinator only); durable at the next
        watermark flush — immediately when flush_groups == 1."""
        if jax.process_index() != 0:
            return
        rec: dict[str, Any] = {"cells": {
            c.key(): dataclasses.asdict(m) for c, m in cells.items()
        }}
        if timing is not None:
            rec["timing"] = timing.row()
        line = json.dumps(rec)
        self._buf.append(line)
        self._buf_bytes += len(line) + 1
        if len(self._buf) >= self.flush_groups \
                or self._buf_bytes >= self.flush_bytes:
            self.flush()

    def flush(self) -> None:
        """Write every buffered record in one append + one fsync.

        The whole coalesced batch lands in a single write() after the torn
        tail (if any) is truncated, so a kill during the flush still leaves
        at worst one torn LINE — the load()-side recovery contract is
        unchanged from the per-group-fsync journal.
        """
        if not self._buf or jax.process_index() != 0:
            return
        self.path.parent.mkdir(parents=True, exist_ok=True)
        lines = []
        if not self._drop_torn_tail():
            lines.append(json.dumps({
                "kind": "fleet-journal",
                "version": self.VERSION,
                "schema": sorted(
                    f.name for f in dataclasses.fields(SimMetrics)
                ),
            }))
        lines.extend(self._buf)
        with self.path.open("a") as f:
            f.write("".join(ln + "\n" for ln in lines))
            f.flush()
            os.fsync(f.fileno())
        self._buf.clear()
        self._buf_bytes = 0

    def close(self) -> None:
        self.flush()


class FleetRunner:
    """Run SweepPlans over a device mesh with pipelined trace staging.

    mesh            1-D "fleet" mesh (default: make_fleet_mesh over all
                    devices; built lazily so constructing a runner never
                    touches jax device state). A multi-process mesh
                    (make_fleet_mesh(processes=N)) works transparently: every
                    process stages the full host batch, owns its device
                    shards, and retire all-gathers each group's (tiny) stats
                    back to every process.
    prefetch_depth  how many groups may be staged-but-not-retired at once:
                    a background prepare thread generates traces, stages
                    them sharded, and resolves the compiled executable up to
                    this many groups ahead of retirement. 2 reproduces the
                    classic double buffer's memory bound; 1 is fully serial.
    double_buffer   legacy alias: False is prefetch_depth=1.
    pipeline        False disables the prepare thread, compile cache, and
                    staging pool, restoring the pre-pipeline inline path —
                    the differential reference the pipelined path is tested
                    against (bit-identical by tests/test_fleet*.py).
    compile_cache   CompileCache instance (default: the process-wide
                    COMPILE_CACHE, so sequential runners share compiles).

    Construction also arms jax's persistent compilation cache
    (repro.utils.compile_cache), so resumed or repeated sweeps in fresh
    processes skip XLA for every signature compiled before. After a run,
    `timings` holds one GroupTiming per retired group.
    """

    def __init__(self, mesh=None, double_buffer: bool = True, *,
                 pipeline: bool = True, prefetch_depth: int | None = None,
                 compile_cache: CompileCache | None = None):
        if prefetch_depth is None:
            prefetch_depth = 2 if double_buffer else 1
        if prefetch_depth < 1:
            raise ValueError(
                f"FleetRunner: prefetch_depth must be >= 1 (got "
                f"{prefetch_depth}); 1 is already the serial pipeline"
            )
        self._mesh = mesh
        self.pipeline = pipeline
        self.prefetch_depth = prefetch_depth
        self.compile_cache = compile_cache or COMPILE_CACHE
        self.timings: list[GroupTiming] = []
        self._staging_pool = _StagingPool()
        enable_compile_cache()

    @property
    def double_buffer(self) -> bool:
        return self.prefetch_depth > 1

    @property
    def mesh(self):
        if self._mesh is None:
            self._mesh = make_fleet_mesh()
        return self._mesh

    # -- staging ------------------------------------------------------------

    def _stage(self, group: FleetGroup):
        """Host trace generation + one sharded device_put per group.

        Runs concurrently with the previous group's device scan (the scan was
        dispatched asynchronously) — this host/device overlap is the whole
        point of the double buffer. Fused-generation groups
        (spec.source != None) stage only (states, seeds): their traces are
        synthesized inside the sharded scan itself.
        """
        mesh = self.mesh
        if group.spec.source is not None:
            simloop.require_uniform_meta(
                [trace_mod.probe_meta(c.app, c.accesses) for c in group.cells]
                + [group.meta],
                [c.label for c in group.cells] + ["probe"],
            )
            batch = np.asarray([c.seed for c in group.cells], np.int32)
        else:
            chunk_list, metas = [], []
            for cell in group.cells:
                chunks, meta = simloop.make_chunks_np(
                    cell.app, cell.policy, cell.mc, cell.seed,
                    cell.intervals, cell.accesses,
                )
                chunk_list.append(chunks)
                metas.append(meta)
            simloop.require_uniform_meta(
                metas + [group.meta], [c.label for c in group.cells] + ["probe"]
            )
            batch = jax.tree.map(lambda *xs: np.stack(xs), *chunk_list)
        pad = -len(group.cells) % mesh.devices.size
        batch = _pad_fleet(batch, pad)

        state0 = jax.tree.map(np.asarray, simloop.engine_init(group.spec))
        states = jax.tree.map(
            lambda x: np.broadcast_to(x, (len(group.cells) + pad,) + x.shape),
            state0,
        )
        target = (states, batch)
        shardings = batch_shardings(target, mesh)
        if _mesh_is_multiprocess(mesh):
            # device_put cannot target non-addressable devices; every process
            # staged the same full host batch, so each contributes exactly
            # the shards its local devices own.
            return jax.tree.map(
                lambda x, s: jax.make_array_from_callback(
                    np.shape(x), s,
                    lambda idx, _x=x: np.ascontiguousarray(_x[idx]),
                ),
                target, shardings,
            )
        return jax.device_put(target, shardings)

    def _stage_pooled(self, group: FleetGroup):
        """Pipelined staging: _stage, with pooled padded host chunk buffers.

        Returns (staged, pool_key, bufs); the caller releases (pool_key,
        bufs) back to the staging pool once the group retires. Per-cell
        chunks are written straight into the padded buffer (no np.stack +
        re-pad copies) and padding lanes repeat the last cell, exactly like
        _pad_fleet. Fused groups stage only the (tiny) seed vector — nothing
        to pool.
        """
        mesh = self.mesh
        if group.spec.source is not None:
            return self._stage(group), None, None
        pad = -len(group.cells) % mesh.devices.size
        n = len(group.cells) + pad
        ii = group.intervals
        aa = group.meta["accesses_per_interval"]
        pool_key = (n, ii, aa)
        bufs = self._staging_pool.acquire(pool_key, lambda: simloop.TraceChunks(
            sp=np.empty((n, ii, aa), np.int32),
            page=np.empty((n, ii, aa), np.int32),
            vpn=np.empty((n, ii, aa), np.int32),
            is_write=np.empty((n, ii, aa), bool),
            in_dram=np.empty((n, ii, aa), bool),
        ))
        metas = []
        for i, cell in enumerate(group.cells):
            chunks, meta = simloop.make_chunks_np(
                cell.app, cell.policy, cell.mc, cell.seed,
                cell.intervals, cell.accesses,
            )
            for dst, src in zip(bufs, chunks):
                dst[i] = src
            metas.append(meta)
        for j in range(len(group.cells), n):
            for dst in bufs:
                dst[j] = dst[len(group.cells) - 1]
        simloop.require_uniform_meta(
            metas + [group.meta], [c.label for c in group.cells] + ["probe"]
        )
        state0 = jax.tree.map(np.asarray, simloop.engine_init(group.spec))
        states = jax.tree.map(
            lambda x: np.broadcast_to(x, (n,) + x.shape), state0
        )
        target = (states, bufs)
        shardings = batch_shardings(target, mesh)
        if _mesh_is_multiprocess(mesh):
            staged = jax.tree.map(
                lambda x, s: jax.make_array_from_callback(
                    np.shape(x), s,
                    lambda idx, _x=x: np.ascontiguousarray(_x[idx]),
                ),
                target, shardings,
            )
        else:
            staged = jax.device_put(target, shardings)
        return staged, pool_key, bufs

    def _launch(self, group: FleetGroup):
        """Stage one group and dispatch its sharded scan (async) to the mesh."""
        states, batch = self._stage(group)
        if group.spec.source is not None:
            fn = _sharded_fused_fn(group.spec, group.intervals, self.mesh)
        else:
            fn = _sharded_fleet_fn(group.spec, self.mesh)
        return fn(states, batch)  # async dispatch: returns before the mesh finishes

    # -- retire -------------------------------------------------------------

    def _retire(self, group: FleetGroup, counters, stats, out: dict):
        """Block on one group's device results and finalize per-cell metrics."""
        if _mesh_is_multiprocess(self.mesh):
            counters, stats = _replicate_fn(self.mesh)((counters, stats))
        out.update(group_metrics(group, counters, stats))

    # -- the sweep ----------------------------------------------------------

    def run(
        self,
        plan: SweepPlan,
        *,
        stream: bool = False,
        journal: str | os.PathLike | FleetJournal | None = None,
    ) -> "FleetResult":
        """Execute every cell of the plan; metrics come back in plan order.

        A pipelined runner (the default) always executes through `run_iter`'s
        prefetch pipeline; `stream`/`journal` only add incremental retirement
        semantics for the caller and checkpointing. With `pipeline=False` and
        neither, the pre-pipeline inline barrier loop runs instead — kept
        verbatim as the differential reference every pipelined path is tested
        against (all paths are bit-identical).
        """
        if stream or journal is not None or self.pipeline:
            metrics = dict(self.run_iter(plan, journal=journal))
            return FleetResult(
                cells=tuple(dict.fromkeys(plan.cells)), metrics=metrics
            )
        self.timings = []
        groups = plan_groups(plan)
        metrics: dict[SweepCell, SimMetrics] = {}
        in_flight: collections.deque = collections.deque()
        for group in groups:
            finals, stats = self._launch(group)
            in_flight.append((group, finals.sim.counters, stats))
            while len(in_flight) >= (2 if self.double_buffer else 1):
                self._retire(*in_flight.popleft(), metrics)
        while in_flight:
            self._retire(*in_flight.popleft(), metrics)
        return FleetResult(cells=tuple(dict.fromkeys(plan.cells)), metrics=metrics)

    def run_iter(
        self,
        plan: SweepPlan,
        *,
        journal: str | os.PathLike | FleetJournal | None = None,
    ) -> Iterator[tuple[SweepCell, SimMetrics]]:
        """Stream (cell, metrics) pairs as each compile-signature group
        retires, instead of blocking until the whole plan finishes.

        Staging and compilation run in the prefetch pipeline (or the legacy
        double buffer with `pipeline=False`), so consumers (figure renderers,
        CSV writers, progress bars) overlap with device work. With `journal`,
        every retired group is appended to the checkpoint (coalesced; durable
        at the journal's flush watermark and whenever this generator
        finalizes — including close()) and groups already recorded there are
        replayed from disk (yielded up front, in plan order) without staging
        a single trace. Per-group GroupTimings accumulate on `self.timings`.
        """
        if journal is not None and not isinstance(journal, FleetJournal):
            journal = FleetJournal(journal)
        self.timings = []
        groups = plan_groups(plan)
        pending: list[FleetGroup] = groups
        try:
            if journal is not None:
                recorded = journal.load()
                if _mesh_is_multiprocess(self.mesh):
                    recorded = _sync_journal_view(recorded)
                pending = []
                for group in groups:
                    got = {c: recorded.get(c.key()) for c in group.cells}
                    if all(m is not None for m in got.values()):
                        yield from got.items()  # resumed from the checkpoint
                    else:
                        pending.append(group)
            if self.pipeline:
                yield from self._pipeline_iter(pending, journal)
            else:
                yield from self._legacy_iter(pending, journal)
        finally:
            if journal is not None:
                journal.flush()

    def _legacy_iter(self, pending, journal):
        """The pre-pipeline inline double buffer (differential reference).

        Timings are attributed coarser than the pipeline's: _launch folds
        trace staging, any jit compile, and dispatch into stage_s (there is
        no compile cache on this path), and scan_s is the host wall blocked
        at retire.
        """
        in_flight: collections.deque = collections.deque()

        def retire_next():
            out: dict[SweepCell, SimMetrics] = {}
            group, counters, stats, stage_s = in_flight.popleft()
            t0 = time.perf_counter()
            jax.block_until_ready((counters, stats))
            t1 = time.perf_counter()
            self._retire(group, counters, stats, out)
            cell0 = group.cells[0]
            timing = GroupTiming(
                label=f"{cell0.app}/{cell0.policy}",
                signature=group_signature(
                    group, int(jax.tree.leaves(stats)[0].shape[0]), self.mesh
                ),
                cells=len(group.cells),
                stage_s=stage_s,
                compile_s=0.0,
                scan_s=t1 - t0,
                retire_s=time.perf_counter() - t1,
                compile_cached=False,
            )
            self.timings.append(timing)
            if journal is not None:
                journal.append(out, timing=timing)
            return out.items()

        for group in pending:
            t0 = time.perf_counter()
            finals, stats = self._launch(group)
            in_flight.append(
                (group, finals.sim.counters, stats, time.perf_counter() - t0)
            )
            while len(in_flight) >= (2 if self.double_buffer else 1):
                yield from retire_next()
        while in_flight:
            yield from retire_next()

    def _pipeline_iter(self, pending, journal):
        """The pipelined engine: a prepare thread stages + compiles ahead.

        One background thread walks the pending groups in plan order: for
        each it generates host traces into a pooled buffer, stages them
        sharded to the mesh, and resolves the group's compiled executable
        (CompileCache) — at most `prefetch_depth` groups ahead of
        retirement, so staged memory stays bounded. The MAIN thread alone
        dispatches the (async) sharded scans and retires them, in plan
        order, so on a multi-process mesh collectives issue in the same
        order on every process. On a multicore host the next group's trace
        generation and compile overlap the in-flight scan; either way,
        repeated signatures skip compilation entirely.
        """
        slots = threading.Semaphore(self.prefetch_depth)
        ready: queue.Queue = queue.Queue()
        stop = threading.Event()

        def prepare():
            try:
                for group in pending:
                    slots.acquire()
                    if stop.is_set():
                        return
                    t0 = time.perf_counter()
                    staged, pool_key, bufs = self._stage_pooled(group)
                    t1 = time.perf_counter()
                    exe, sig, compile_s, cached = \
                        self.compile_cache.get_or_compile(
                            group, staged, self.mesh)
                    ready.put((group, staged, exe, sig, pool_key, bufs,
                               t1 - t0, compile_s, cached))
                ready.put(None)
            except BaseException as e:  # re-raised on the consuming side
                ready.put(e)

        worker = threading.Thread(
            target=prepare, name="fleet-prepare", daemon=True
        )
        in_flight: collections.deque = collections.deque()

        def retire_next():
            (group, counters, stats, sig, pool_key, bufs,
             stage_s, compile_s, cached) = in_flight.popleft()
            t0 = time.perf_counter()
            jax.block_until_ready((counters, stats))
            t1 = time.perf_counter()
            out: dict[SweepCell, SimMetrics] = {}
            self._retire(group, counters, stats, out)
            if pool_key is not None:
                self._staging_pool.release(pool_key, bufs)
            slots.release()
            cell0 = group.cells[0]
            timing = GroupTiming(
                label=f"{cell0.app}/{cell0.policy}",
                signature=sig,
                cells=len(group.cells),
                stage_s=stage_s,
                compile_s=compile_s,
                scan_s=t1 - t0,
                retire_s=time.perf_counter() - t1,
                compile_cached=cached,
            )
            self.timings.append(timing)
            if journal is not None:
                journal.append(out, timing=timing)
            return out.items()

        worker.start()
        try:
            while True:
                item = ready.get()
                if item is None:
                    break
                if isinstance(item, BaseException):
                    raise item
                (group, staged, exe, sig, pool_key, bufs,
                 stage_s, compile_s, cached) = item
                finals, stats = exe(*staged)  # async dispatch
                del staged  # states were donated; drop the host reference
                in_flight.append((group, finals.sim.counters, stats, sig,
                                  pool_key, bufs, stage_s, compile_s, cached))
                while len(in_flight) >= self.prefetch_depth:
                    yield from retire_next()
            while in_flight:
                yield from retire_next()
        finally:
            stop.set()
            slots.release()  # unblock a prepare thread parked on acquire
            worker.join(timeout=60)

    # -- trace calibration (Fig. 1 / Tables I-II, no simulation) ------------

    def calibration(self, plan: SweepPlan) -> dict[SweepCell, dict]:
        """Per-cell trace-calibration statistics (host-only, no device work).

        Lets the trace-validation figures declare the same SweepPlan grid as
        the simulation figures and render rows from one API.
        """
        return {
            cell: trace_calibration_stats(
                trace_mod.generate(cell.app, cell.seed, interval=1,
                                   accesses=cell.accesses)
            )
            for cell in plan.cells
        }


def trace_calibration_stats(tr) -> dict[str, Any]:
    """Paper Fig. 1 / Tables I-II statistics of one generated trace."""
    sp_touched: dict[int, set] = {}
    for s, p in zip(tr.sp, tr.page):
        sp_touched.setdefault(int(s), set()).add(int(p))
    touched = np.array([len(v) for v in sp_touched.values()])
    counts = np.bincount(tr.vpn.astype(np.int64), minlength=tr.footprint_pages)
    order = np.argsort(-counts)
    csum = np.cumsum(counts[order])
    n_hot = int(np.searchsorted(csum, 0.70 * csum[-1])) + 1
    ws_pages = int((counts > 0).sum())
    return {
        "sp_with_le32_touched_pct": round(float((touched <= 32).mean() * 100), 1),
        "median_touched_per_sp": int(np.median(touched)),
        "hot_page_pct_measured": round(100 * n_hot / max(ws_pages, 1), 2),
        "working_set_pages": ws_pages,
    }


@dataclasses.dataclass(frozen=True)
class FleetResult:
    """Cell -> SimMetrics mapping in plan order, sliceable by field or tag."""

    cells: tuple[SweepCell, ...]
    metrics: Mapping[SweepCell, SimMetrics]

    def __len__(self) -> int:
        return len(self.cells)

    def __iter__(self) -> Iterator[SweepCell]:
        return iter(self.cells)

    def items(self):
        return [(c, self.metrics[c]) for c in self.cells]

    def __getitem__(self, key) -> SimMetrics:
        if isinstance(key, SweepCell):
            return self.metrics[key]
        app, policy, *rest = key
        return self.one(app=app, policy=policy,
                        **({"seed": rest[0]} if rest else {}))

    def apps(self) -> list[str]:
        return sorted({c.app for c in self.cells})

    def policies(self) -> list[str]:
        out: list[str] = []
        for c in self.cells:
            if c.policy not in out:
                out.append(c.policy)
        return out

    def select(self, **filters) -> list[tuple[SweepCell, SimMetrics]]:
        """Cells matching every filter; SweepCell field names match fields,
        anything else matches the cell's tags."""
        fields = {f.name for f in dataclasses.fields(SweepCell)}

        def ok(cell: SweepCell) -> bool:
            for k, v in filters.items():
                got = getattr(cell, k) if k in fields else cell.tag.get(k)
                if got != v:
                    return False
            return True

        return [(c, self.metrics[c]) for c in self.cells if ok(c)]

    def one(self, **filters) -> SimMetrics:
        hits = self.select(**filters)
        if len(hits) != 1:
            raise KeyError(
                f"{filters} matched {len(hits)} cells"
                + (f" (e.g. {[c.label for c, _ in hits[:4]]})" if hits else "")
            )
        return hits[0][1]

    def rows(self, **filters) -> list[dict[str, Any]]:
        """SimMetrics.row() per matching cell, annotated with seed + tags."""
        return [
            {**m.row(), "seed": c.seed, **c.tag}
            for c, m in self.select(**filters)
        ]
