"""Engine-in-the-loop serving autotuner over the ControlPolicy surface.

The ROADMAP's open item: Layer B's interval controller is the same jitted
`engine.control` path as Layer A, so its knobs (interval_steps, top_n,
threshold, ...) can be searched *against live decode traffic* instead of being
hand-set. This module closes that loop:

  MassTrace      a recorded decode attention-mass stream — one [B, nblk] row
                 per decode step, captured from a real model run by
                 `serving.rainbow_decode.record_mass_trace` (the exact array
                 observe_block_mass saw);
  TunePlan       a declarative search space over ControlPolicy fields with
                 successive-halving refinement (short trace prefixes eliminate
                 weak candidates before anyone pays for the full trace);
  evaluate       engine-in-the-loop replay: for each candidate policy the
                 controller itself (observe_block_mass -> end_interval_promote,
                 i.e. the SAME engine.control path serving runs) is replayed
                 over the trace on zero-payload KV state, and the serving cost
                 model (migration.TimingParams, "v5e-serving" preset) scores
                 the access stream it produces — mass-weighted reads at t_dr
                 (hot pool) vs t_nr (capacity pool) plus t_mig per promotion;
  autotune       the search driver; its TuneResult.tuned_policy() plugs
                 straight back into PagedConfig / launch.serve --autotune.

Candidates that share static shapes (top_n, max_promotions, hot_slots, ...)
fuse into one compiled group; interval_steps and threshold_init are *traced*
inside the replay, so a whole group evaluates as one vmap. Like engine.fleet,
the same vmapped body can instead be shard_mapped over the 1-D "fleet" device
mesh (`runner="sharded"`) — per shard it is the identical program, so the two
paths are bit-identical, padding included.
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
import math
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import counting, migration
from repro.core.migration import TimingParams, preset_timing
from repro.core.remap import remap_init, translate
from repro.engine.policy import ControlPolicy
from repro.memory.kvcache import (
    PagedConfig,
    RainbowKV,
    end_interval_promote,
    observe_block_mass,
    quantize_mass,
)

# ---------------------------------------------------------------------------
# Recorded decode traffic
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MassTrace:
    """A recorded per-block attention-mass stream (host-side numpy).

    mass[t, b, j] is the softmax mass KV block j of sequence b received at
    decode step t, summed over layers and heads — the access stream of the
    paper's memory controller in Layer B units. `start_length` is the sequence
    length before step 0 (0 when recording covers the prompt).
    """

    mass: np.ndarray  # float32[T, B, nblk]
    block_size: int
    start_length: int = 0

    @property
    def steps(self) -> int:
        return self.mass.shape[0]

    @property
    def batch(self) -> int:
        return self.mass.shape[1]

    @property
    def blocks_per_seq(self) -> int:
        return self.mass.shape[2]

    def prefix(self, steps: int) -> "MassTrace":
        """The first `steps` decode steps (successive-halving rungs)."""
        return MassTrace(
            mass=self.mass[:steps],
            block_size=self.block_size,
            start_length=self.start_length,
        )


# ---------------------------------------------------------------------------
# Search space
# ---------------------------------------------------------------------------

_POLICY_FIELDS = {f.name for f in dataclasses.fields(ControlPolicy)}


@dataclasses.dataclass(frozen=True)
class TunePlan:
    """A declarative search space over ControlPolicy fields.

    space  ((field, (value, ...)), ...) — the cartesian grid, applied over
           `base` with ControlPolicy.replace (so every candidate re-validates)
    rungs  successive-halving rounds; rung r evaluates survivors on the first
           T // eta**(rungs-1-r) trace steps and keeps the best 1/eta
    eta    halving factor
    """

    space: tuple[tuple[str, tuple[Any, ...]], ...]
    base: ControlPolicy = dataclasses.field(default_factory=ControlPolicy)
    rungs: int = 2
    eta: int = 2

    def __post_init__(self):
        bad = [k for k, _ in self.space if k not in _POLICY_FIELDS]
        if bad:
            raise ValueError(
                f"TunePlan: unknown ControlPolicy fields {bad}; "
                f"searchable: {sorted(_POLICY_FIELDS)}"
            )
        if self.rungs < 1 or self.eta < 2:
            raise ValueError(
                f"TunePlan: need rungs >= 1 and eta >= 2 "
                f"(got rungs={self.rungs}, eta={self.eta})"
            )

    @staticmethod
    def grid(base: ControlPolicy | None = None, *, rungs: int = 2,
             eta: int = 2, **space: Sequence[Any]) -> "TunePlan":
        """`TunePlan.grid(interval_steps=(2, 8), threshold_init=(0.0, 64.0))`."""
        return TunePlan(
            space=tuple(sorted((k, tuple(v)) for k, v in space.items())),
            base=base if base is not None else ControlPolicy(),
            rungs=rungs,
            eta=eta,
        )

    def candidates(self) -> tuple[ControlPolicy, ...]:
        """The full candidate grid, base-first ordering within each field."""
        if not self.space:
            return (self.base.validate(),)
        names = [k for k, _ in self.space]
        grids = [v for _, v in self.space]
        return tuple(
            self.base.replace(**dict(zip(names, combo)))
            for combo in itertools.product(*grids)
        )


# ---------------------------------------------------------------------------
# Engine-in-the-loop replay
# ---------------------------------------------------------------------------


def _group_signature(pol: ControlPolicy) -> ControlPolicy:
    """Candidates equal under this signature share one compiled replay group
    (interval_steps and threshold_init are traced inside the replay)."""
    return dataclasses.replace(pol, interval_steps=1, threshold_init=0.0)


def _replay_pcfg(trace: MassTrace, signature: ControlPolicy) -> PagedConfig:
    return PagedConfig(
        block_size=trace.block_size,
        blocks_per_seq=trace.blocks_per_seq,
        policy=signature,
    )


def _controller_kv(pcfg: PagedConfig, batch: int, start_length: int) -> RainbowKV:
    """Controller-only KV state: the full RainbowKV pytree with ZERO-layer
    pools, so end_interval_promote runs the exact serving control path
    (plan_and_apply, remap install/evict, monitor rotation) with free payload
    copies — the replay is the controller, not a model of it."""
    nblk = pcfg.blocks_per_seq
    cap = jnp.zeros((0, batch * nblk, pcfg.block_size, 1, 1), jnp.float32)
    hot = jnp.zeros((0, pcfg.hot_slots, pcfg.block_size, 1, 1), jnp.float32)
    return RainbowKV(
        cap_k=cap, cap_v=cap, hot_k=hot, hot_v=hot,
        remap=remap_init(batch, nblk),
        s1=counting.stage1_init(batch),
        s2=counting.stage2_init(pcfg.top_n, nblk),
        dram=migration.dram_init(pcfg.hot_slots),
        threshold=jnp.zeros((), jnp.float32),
        length=jnp.asarray(start_length, jnp.int32),
        step_in_interval=jnp.zeros((), jnp.int32),
    )


def _replay_one(pcfg: PagedConfig, kv: RainbowKV, interval_steps: jax.Array,
                mass: jax.Array, timing: TimingParams):
    """Replay the interval controller over one trace; return modeled cost.

    Per step: every valid block's quantized mass (the same 64x quantization
    observe_block_mass applies) is served from the tier the remap table says
    it lives in (t_dr hot pool vs t_nr capacity pool); each admitted promotion
    pays t_mig. Evicted KV blocks are clean (writes mirror into the capacity
    copy), so eviction costs only the remap-pointer write — §III-E's fast
    path — and is not charged.
    """
    nblk = pcfg.blocks_per_seq
    batch = kv.s1.counts.shape[0]
    sp_grid = jnp.arange(batch, dtype=jnp.int32)[:, None].repeat(nblk, 1)
    pg_grid = jnp.arange(nblk, dtype=jnp.int32)[None, :].repeat(batch, 0)

    def step(carry, mass_t):
        kv, cost = carry
        q = quantize_mass(mass_t).astype(jnp.float32)  # the counters' stream
        valid = pg_grid <= (kv.length // pcfg.block_size)
        resident, _ = translate(kv.remap, sp_grid, pg_grid)
        lat = jnp.where(resident, timing.t_dr, timing.t_nr)
        cost = cost + jnp.sum(jnp.where(valid, q * lat, 0.0))

        kv = observe_block_mass(kv, pcfg, mass_t)
        kv = dataclasses.replace(kv, length=kv.length + 1)

        def do_promote(kv_):
            new, rep = end_interval_promote(kv_, pcfg, timing)
            return new, rep["promoted"], rep["evicted"]

        def skip(kv_):
            return kv_, jnp.int32(0), jnp.int32(0)

        kv, n_prom, n_ev = jax.lax.cond(
            kv.step_in_interval >= interval_steps, do_promote, skip, kv
        )
        cost = cost + n_prom.astype(jnp.float32) * timing.t_mig
        return (kv, cost), (n_prom, n_ev)

    (kv, cost), (proms, evs) = jax.lax.scan(step, (kv, jnp.float32(0.0)), mass)
    return cost, proms.sum(), evs.sum()


def _vmapped_replay(pcfg: PagedConfig):
    return jax.vmap(
        lambda kv, iv, mass, timing: _replay_one(pcfg, kv, iv, mass, timing),
        in_axes=(0, 0, None, None),
    )


@functools.partial(jax.jit, static_argnames=("pcfg",))
def _eval_group_vmap(pcfg: PagedConfig, states: RainbowKV, ivals: jax.Array,
                     mass: jax.Array, timing: TimingParams):
    return _vmapped_replay(pcfg)(states, ivals, mass, timing)


@functools.lru_cache(maxsize=None)
def _sharded_replay_fn(pcfg: PagedConfig, mesh):
    """shard_map of the SAME vmapped replay body over the fleet mesh — per
    shard it is exactly _eval_group_vmap's program, so sharded evaluation is
    bit-identical to the one-device vmap path (cf. engine.fleet)."""
    fn = jax.shard_map(
        _vmapped_replay(pcfg),
        mesh=mesh,
        in_specs=(P("fleet"), P("fleet"), P(), P()),
        out_specs=(P("fleet"), P("fleet"), P("fleet")),
        check_vma=False,  # per-candidate replays, no collectives: nothing to check
    )
    return jax.jit(fn)


def _group_states(pcfg: PagedConfig, batch: int, start_length: int,
                  thresholds: np.ndarray) -> RainbowKV:
    kv0 = _controller_kv(pcfg, batch, start_length)
    states = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (len(thresholds),) + x.shape), kv0
    )
    return dataclasses.replace(
        states, threshold=jnp.asarray(thresholds, jnp.float32)
    )


def evaluate(
    trace: MassTrace,
    policies: Sequence[ControlPolicy],
    *,
    timing: TimingParams | None = None,
    runner: str = "vmap",
    mesh=None,
) -> list[dict[str, float]]:
    """Replay every candidate policy against the trace; one row per policy
    (plan order): modeled cost per decode step, promotions, evictions.

    runner="vmap" evaluates each static-shape group as one vmap on the default
    device; runner="sharded" shard_maps the same body over the fleet mesh.
    """
    if runner not in ("vmap", "sharded"):
        raise ValueError(f"unknown runner {runner!r}; use 'vmap' or 'sharded'")
    timing = timing if timing is not None else preset_timing("v5e-serving")
    mass = jnp.asarray(trace.mass, jnp.float32)

    # group candidates by static replay signature (first-seen order)
    groups: dict[ControlPolicy, list[int]] = {}
    for i, pol in enumerate(policies):
        # per-candidate validation against the trace geometry, loudly
        _replay_pcfg(trace, pol.validate())
        groups.setdefault(_group_signature(pol), []).append(i)

    if runner == "sharded" and mesh is None:
        from repro.launch.mesh import make_fleet_mesh

        mesh = make_fleet_mesh()

    rows: list[dict[str, float] | None] = [None] * len(policies)
    for sig, idxs in groups.items():
        pcfg = _replay_pcfg(trace, sig)
        ivals = np.asarray([policies[i].interval_steps for i in idxs], np.int32)
        thrs = np.asarray([policies[i].threshold_init for i in idxs], np.float32)
        if runner == "vmap":
            states = _group_states(pcfg, trace.batch, trace.start_length, thrs)
            cost, prom, ev = _eval_group_vmap(
                pcfg, states, jnp.asarray(ivals), mass, timing
            )
        else:
            pad = -len(idxs) % mesh.devices.size
            if pad:
                ivals = np.concatenate([ivals, np.repeat(ivals[-1:], pad)])
                thrs = np.concatenate([thrs, np.repeat(thrs[-1:], pad)])
            states = _group_states(pcfg, trace.batch, trace.start_length, thrs)
            cost, prom, ev = _sharded_replay_fn(pcfg, mesh)(
                states, jnp.asarray(ivals), mass, timing
            )
        cost, prom, ev = map(np.asarray, (cost, prom, ev))
        for j, i in enumerate(idxs):  # padding lanes are dropped
            rows[i] = {
                "cost_per_step": float(cost[j]) / max(trace.steps, 1),
                "total_cost": float(cost[j]),
                "promotions": int(prom[j]),
                "evictions": int(ev[j]),
            }
    return rows  # type: ignore[return-value]


# ---------------------------------------------------------------------------
# Search driver
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TuneResult:
    """Outcome of one autotune run; `tuned_policy()` is the serving plug-in."""

    plan: TunePlan
    best: ControlPolicy
    best_cost: float  # modeled cost per decode step on the full trace
    baseline: ControlPolicy
    baseline_cost: float
    table: tuple[dict[str, Any], ...]  # per (rung, candidate) evaluation rows

    def tuned_policy(self) -> ControlPolicy:
        return self.best

    @property
    def improved(self) -> bool:
        return self.best_cost < self.baseline_cost

    def summary(self) -> str:
        gain = 100.0 * (1.0 - self.best_cost / max(self.baseline_cost, 1e-12))
        return (
            f"tuned {self.best_cost:.1f} vs baseline {self.baseline_cost:.1f} "
            f"ns/step ({gain:+.1f}%) with interval_steps="
            f"{self.best.interval_steps}, top_n={self.best.top_n}, "
            f"threshold_init={self.best.threshold_init}"
        )


def autotune(
    plan: TunePlan,
    trace: MassTrace,
    *,
    timing: TimingParams | None = None,
    runner: str = "vmap",
    mesh=None,
    baseline: ControlPolicy | None = None,
) -> TuneResult:
    """Successive-halving search of `plan` against a recorded mass trace.

    Rung r evaluates the surviving candidates on the first
    T // eta**(rungs-1-r) steps and keeps the best ceil(n/eta); the final rung
    runs the full trace and the argmin (ties broken by candidate index, so
    vmap and sharded runs pick the identical winner) becomes the result.
    """
    timing = timing if timing is not None else preset_timing("v5e-serving")
    cands = list(plan.candidates())
    baseline = (baseline or plan.base).validate()
    survivors = list(range(len(cands)))
    table: list[dict[str, Any]] = []

    for r in range(plan.rungs):
        steps = max(1, trace.steps // (plan.eta ** (plan.rungs - 1 - r)))
        sub = trace.prefix(steps)
        rows = evaluate(sub, [cands[i] for i in survivors],
                        timing=timing, runner=runner, mesh=mesh)
        ranked = sorted(
            zip((row["total_cost"] for row in rows), survivors, rows),
            key=lambda t: (t[0], t[1]),
        )
        for c, i, row in ranked:
            table.append({
                "rung": r, "steps": steps, "candidate": i,
                "policy": cands[i], **row,
            })
        keep = 1 if r == plan.rungs - 1 else max(
            1, math.ceil(len(survivors) / plan.eta)
        )
        survivors = [i for _, i, _ in ranked[:keep]]
        final_rows = {i: row for _, i, row in ranked}

    best_idx = survivors[0]
    best_cost = final_rows[best_idx]["cost_per_step"]
    # reuse the final (full-trace) rung when the baseline was a candidate there
    base_row = next(
        (final_rows[i] for i in final_rows if cands[i] == baseline), None
    )
    if base_row is None:
        [base_row] = evaluate(trace, [baseline],
                              timing=timing, runner=runner, mesh=mesh)
    return TuneResult(
        plan=plan,
        best=cands[best_idx],
        best_cost=best_cost,
        baseline=baseline,
        baseline_cost=base_row["cost_per_step"],
        table=tuple(table),
    )
