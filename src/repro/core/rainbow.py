"""RainbowController: the paper's memory-controller + OS modules as one JAX pytree.

Composes the pieces of §III into a single functional controller:

  observe(accesses)  -> stage-1 superpage counting, stage-2 small-page counting for
                        the currently-monitored hot superpages, DRAM-tier counter
                        updates (for Eq. 2 victims).
  end_interval()     -> top-N hot-superpage selection (next interval's monitor set),
                        hot-page classification, utility-admission (Eq. 1/2) against
                        the free/clean/dirty slot manager, remap/bitmap install and
                        evict, adaptive threshold update.
  interval_step()    -> observe + end_interval fused into one scannable function:
                        `engine.simloop` runs a whole simulation as a single
                        lax.scan over these steps.

Both the Layer-A simulator and the Layer-B serving runtime drive this control
loop; the phase bodies live once, in `repro.engine.control`, and only the
meaning of "access" differs (post-LLC memory reference vs KV-block read).
"""
from __future__ import annotations

import dataclasses
from typing import TYPE_CHECKING, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import counting, migration
from repro.core.counting import Stage1State, Stage2State
from repro.core.migration import DramState, MigrationPlan, TimingParams
from repro.core.remap import RemapState
from repro.utils import pytree_dataclass, static_field

if TYPE_CHECKING:  # runtime import would cycle (see _control_cfg)
    from repro.engine.policy import ControlPolicy


@pytree_dataclass(init=False)
class RainbowConfig:
    """Layer-A controller config: ControlPolicy + superpage geometry.

    The controller knobs live on ONE surface (`engine.policy.ControlPolicy`);
    this config only adds what is specific to the simulator's address space.
    The pre-redesign flat knobs (`top_n`, `dram_slots`, `write_weight`,
    `max_migrations_per_interval`, `counter_backend`) are kept as
    deprecation-shim init kwargs + read-only properties, so existing call
    sites (and `dataclasses.replace` on them) keep working.
    """

    num_superpages: int = static_field(default=1024)
    pages_per_sp: int = static_field(default=512)
    policy: "ControlPolicy" = static_field(default=None)

    def __init__(
        self,
        num_superpages: int = 1024,
        pages_per_sp: int = 512,
        top_n: int | None = None,
        dram_slots: int | None = None,
        write_weight: int | None = None,
        max_migrations_per_interval: int | None = None,
        counter_backend: str | None = None,
        policy=None,
    ):
        from repro.engine.policy import ControlPolicy

        if policy is None:
            # paper §IV-F defaults (N = 100); interval_steps = 1: Layer A
            # closes the controller once per trace chunk
            policy = ControlPolicy(
                interval_steps=1, top_n=100, max_promotions=512,
                hot_slots=4096, write_weight=2,
            )
        legacy = {
            "top_n": top_n,
            "hot_slots": dram_slots,
            "write_weight": write_weight,
            "max_promotions": max_migrations_per_interval,
            "counter_backend": counter_backend,
        }
        overrides = {k: v for k, v in legacy.items() if v is not None}
        if overrides:
            policy = dataclasses.replace(policy, **overrides)
        object.__setattr__(self, "num_superpages", num_superpages)
        object.__setattr__(self, "pages_per_sp", pages_per_sp)
        object.__setattr__(self, "policy", policy.validate("RainbowConfig"))
        self.validate()

    def validate(self) -> "RainbowConfig":
        if self.num_superpages < 1 or self.pages_per_sp < 1:
            raise ValueError(
                "RainbowConfig: num_superpages and pages_per_sp must be >= 1 "
                f"(got {self.num_superpages}, {self.pages_per_sp})"
            )
        return self

    # -- deprecation shims (old flat-knob surface) --------------------------

    @property
    def top_n(self) -> int:
        return self.policy.top_n

    @property
    def dram_slots(self) -> int:
        return self.policy.hot_slots

    @property
    def write_weight(self) -> int:
        return self.policy.write_weight

    @property
    def max_migrations_per_interval(self) -> int:
        return self.policy.max_promotions

    @property
    def counter_backend(self) -> str:
        return self.policy.counter_backend


@pytree_dataclass
class RainbowState:
    s1: Stage1State
    s2_reads: Stage2State
    s2_writes: Stage2State
    dram: DramState
    remap: RemapState
    threshold: jax.Array  # float32 adaptive admission threshold
    interval: jax.Array  # int32 interval counter
    evictions_last: jax.Array  # int32 bidirectional-traffic monitor
    # Cumulative totals are int32 DELIBERATELY: JAX disables x64 by default, so
    # an int64 request would silently produce int32 anyway (with a warning) and
    # make the scan-carry dtype depend on global config. int32 wraps only after
    # 2^31 migrated pages (~8 TB of 4 KB traffic) — far beyond any simulated
    # horizon here. Revisit alongside jax_enable_x64 if that ever changes.
    migrations_total: jax.Array  # int32 cumulative pages migrated in
    evictions_total: jax.Array  # int32 cumulative pages evicted


class IntervalReport(NamedTuple):
    plan: MigrationPlan
    cand_sp: jax.Array
    cand_page: jax.Array
    n_migrated: jax.Array
    n_evicted: jax.Array
    n_dirty_evicted: jax.Array
    threshold: jax.Array


def _control_cfg(cfg: RainbowConfig):
    # Lazy import: repro.core.__init__ imports this module eagerly, and
    # engine.control imports repro.core leaf modules — a module-level import
    # here would cycle on first import of either package.
    from repro.engine import control

    return control, cfg.policy.control_config(
        num_units=cfg.num_superpages, pages_per_unit=cfg.pages_per_sp
    )


def rainbow_init(cfg: RainbowConfig, threshold: float | None = None) -> RainbowState:
    """Fresh controller state; `threshold` defaults to the policy's
    threshold_init (the explicit argument remains as an override shim)."""
    from repro.core import remap as remap_mod

    if threshold is None:
        threshold = cfg.policy.threshold_init
    return RainbowState(
        s1=counting.stage1_init(cfg.num_superpages),
        s2_reads=counting.stage2_init(cfg.top_n, cfg.pages_per_sp),
        s2_writes=counting.stage2_init(cfg.top_n, cfg.pages_per_sp),
        dram=migration.dram_init(cfg.dram_slots),
        remap=remap_mod.remap_init(cfg.num_superpages, cfg.pages_per_sp),
        threshold=jnp.asarray(threshold, jnp.float32),
        interval=jnp.zeros((), jnp.int32),
        evictions_last=jnp.zeros((), jnp.int32),
        migrations_total=jnp.zeros((), jnp.int32),
        evictions_total=jnp.zeros((), jnp.int32),
    )


def observe(
    cfg: RainbowConfig,
    st: RainbowState,
    sp: jax.Array,  # int32[B] superpage id per access
    page: jax.Array,  # int32[B] small page within superpage
    is_write: jax.Array,  # bool[B]
    now: jax.Array,  # int32 logical time (for LRU)
) -> RainbowState:
    """Record one batch of accesses. Accesses to migrated pages are DRAM-tier hits
    (counted on the slot for Eq. 2); the rest are NVM-tier (stage-1/2 counting)."""
    control, ctrl = _control_cfg(cfg)
    s1, s2r, s2w, dram = control.observe_tiers(
        ctrl, st.s1, st.s2_reads, st.s2_writes, st.dram, st.remap,
        sp, page, is_write, now,
    )
    return dataclasses.replace(st, s1=s1, s2_reads=s2r, s2_writes=s2w, dram=dram)


def plan_interval(cfg: RainbowConfig, st: RainbowState, timing: TimingParams):
    """First half of end_interval: classify hot pages + admit migrations.

    Returns the control.plan_and_apply outcome (plan, remap', dram',
    threshold', counts). Split out so the per-phase profiler
    (engine.profile) can time the planning cost separately; end_interval
    composes plan_interval + apply_interval unchanged.
    """
    control, ctrl = _control_cfg(cfg)
    reads, writes = counting.stage2_split_rw(st.s2_reads, st.s2_writes)
    return control.plan_and_apply(
        ctrl, reads, writes, st.s2_reads.psn,
        st.remap, st.dram, st.threshold, timing, now=st.interval,
    )


def apply_interval(
    cfg: RainbowConfig, st: RainbowState, out
) -> tuple[RainbowState, IntervalReport]:
    """Second half of end_interval: rotate monitors + commit controller state."""
    control, ctrl = _control_cfg(cfg)
    s1, new_psn, dram = control.rotate_monitors(ctrl, st.s1, out.dram)

    new_st = dataclasses.replace(
        st,
        s1=s1,
        s2_reads=counting.stage2_begin(new_psn, cfg.pages_per_sp),
        s2_writes=counting.stage2_begin(new_psn, cfg.pages_per_sp),
        dram=dram,
        remap=out.remap,
        threshold=out.threshold,
        interval=st.interval + 1,
        evictions_last=out.n_evicted,
        migrations_total=st.migrations_total + out.n_migrated,
        evictions_total=st.evictions_total + out.n_evicted,
    )
    report = IntervalReport(
        plan=out.plan,
        cand_sp=out.cand_sp,
        cand_page=out.cand_page,
        n_migrated=out.n_migrated,
        n_evicted=out.n_evicted,
        n_dirty_evicted=out.n_dirty,
        threshold=out.threshold,
    )
    return new_st, report


def end_interval(
    cfg: RainbowConfig, st: RainbowState, timing: TimingParams
) -> tuple[RainbowState, IntervalReport]:
    """Close the interval: classify hot pages, admit migrations, rotate monitors."""
    return apply_interval(cfg, st, plan_interval(cfg, st, timing))


def interval_step(
    cfg: RainbowConfig,
    st: RainbowState,
    sp: jax.Array,
    page: jax.Array,
    is_write: jax.Array,
    timing: TimingParams,
) -> tuple[RainbowState, IntervalReport]:
    """One full monitoring interval (observe batch + end_interval), scannable.

    `jax.lax.scan(lambda st, tr: interval_step(cfg, st, *tr, timing), st, chunks)`
    runs an entire simulation device-resident — this is the EngineStep used by
    engine.simloop's rainbow policy program. Its three phases run under the
    named scopes "observe", "plan" and "apply" (engine.profile's phases).
    """
    with jax.named_scope("observe"):
        st = observe(cfg, st, sp, page, is_write, st.interval)
    with jax.named_scope("plan"):
        out = plan_interval(cfg, st, timing)
    with jax.named_scope("apply"):
        return apply_interval(cfg, st, out)


def translate_accesses(
    st: RainbowState, sp: jax.Array, page: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Public vectorized translation (Fig. 6 outcome): (in_fast_tier, slot)."""
    from repro.core import remap as remap_mod

    return remap_mod.translate(st.remap, sp, page)
