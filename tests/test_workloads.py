"""repro.workloads: device-resident trace generators + scenario registry.

The load-bearing guarantee is the DIFFERENTIAL GATE: for every registered
scenario preset, chunks generated in-scan (EngineSpec.source, fused mode)
produce bit-identical SimMetrics to the same generator stream materialized
to host and fed through the staged path — single cell, vmap-over-seeds, and
the 4-device sharded fleet. A scenario that drifted between its two modes
would corrupt every sweep that mixes them.

Generator invariants (shapes, vpn ranges, determinism under jit/vmap,
write-fraction bounds) run as deterministic floors everywhere and as a
hypothesis property layer where hypothesis is installed (the same
optional-dependency convention as tests/test_core_* / test_fleet.py).
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.engine.simloop as simloop
from repro.engine import fleet
from repro.sim import trace as trace_mod
from repro.sim.config import MachineConfig, PAGES_PER_SP
from repro.sim.runner import simulate
from repro.workloads import generators as G
from repro.workloads import scenarios as S

INTERVALS = 2
ACCESSES = 1200


# ---------------------------------------------------------------------------
# Generator invariants: plain-function checks (deterministic floors +
# hypothesis property layer share them)
# ---------------------------------------------------------------------------


def _emit(gen, seed: int, interval: int):
    aux = gen.setup(jnp.int32(seed))
    key = G.interval_key(jnp.int32(seed), jnp.int32(interval))
    pages, wr = gen.emit(aux, key, jnp.int32(interval))
    return np.asarray(pages), np.asarray(wr)


def check_generator_invariants(gen, seed: int = 3, interval: int = 1):
    """Shapes, ranges, dtype, and 5-sigma write-fraction bounds of one emit."""
    gen.validate()
    pages, wr = _emit(gen, seed, interval)
    a = gen.accesses
    assert pages.shape == (a,) and wr.shape == (a,)
    assert pages.dtype == np.int32 and wr.dtype == np.bool_
    assert pages.min() >= 0 and pages.max() < gen.footprint_pages
    ratio = getattr(gen, "write_ratio", None)
    if ratio is None:  # mix: bound by the members' extreme ratios
        ratios = [m.write_ratio for m in gen.members]
        lo, hi = min(ratios), max(ratios)
    else:
        lo = hi = ratio
    sigma = 5.0 * np.sqrt(0.25 / a)  # max Bernoulli var at p=1/2
    assert lo - sigma <= wr.mean() <= hi + sigma, (wr.mean(), lo, hi)


def check_generator_determinism(gen, seed: int = 5, interval: int = 2):
    """Same seed => identical chunks; emit is invariant under jit and vmap."""
    p1, w1 = _emit(gen, seed, interval)
    p2, w2 = _emit(gen, seed, interval)
    np.testing.assert_array_equal(p1, p2)
    np.testing.assert_array_equal(w1, w2)

    def emit(sd, iv):
        aux = gen.setup(sd)
        return gen.emit(aux, G.interval_key(sd, iv), iv)

    pj, wj = jax.jit(emit)(jnp.int32(seed), jnp.int32(interval))
    np.testing.assert_array_equal(np.asarray(pj), p1)
    np.testing.assert_array_equal(np.asarray(wj), w1)

    seeds = jnp.asarray([seed, (seed + 9) % 2**31], jnp.int32)  # stay in int32
    ivs = jnp.full_like(seeds, interval)
    pv, wv = jax.jit(jax.vmap(emit))(seeds, ivs)
    np.testing.assert_array_equal(np.asarray(pv)[0], p1)
    np.testing.assert_array_equal(np.asarray(wv)[0], w1)


SMALL_GENERATORS = [
    G.ZipfHotspot(footprint_pages=2048, accesses=1500, hot_frac=0.03,
                  zipf_alpha=1.2, hot_traffic=0.8, write_ratio=0.3),
    G.PhaseShift(footprint_pages=2048, accesses=1500, ws_frac=0.25,
                 drift_frac=0.5, hot_frac=0.2, write_ratio=0.25),
    G.SequentialScan(footprint_pages=1024, accesses=1500, stride=3,
                     write_ratio=0.1),
    G.PointerChase(footprint_pages=4096, accesses=1500, write_ratio=0.2),
    G.InterleavedMix(members=(
        G.ZipfHotspot(footprint_pages=700, accesses=500, write_ratio=0.4),
        G.SequentialScan(footprint_pages=1024, accesses=500, write_ratio=0.0),
        G.PointerChase(footprint_pages=600, accesses=500, write_ratio=0.2),
    )),
]


@pytest.mark.parametrize("gen", SMALL_GENERATORS,
                         ids=lambda g: type(g).__name__)
def test_generator_invariants_floor(gen):
    check_generator_invariants(gen)
    check_generator_determinism(gen)


def test_different_seeds_and_intervals_differ():
    gen = SMALL_GENERATORS[0]
    p1, _ = _emit(gen, seed=1, interval=0)
    p2, _ = _emit(gen, seed=2, interval=0)
    p3, _ = _emit(gen, seed=1, interval=1)
    assert not np.array_equal(p1, p2)  # fresh key stream per seed
    assert not np.array_equal(p1, p3)  # fold_in moves the stream per interval


def test_seq_scan_resumes_across_intervals():
    gen = G.SequentialScan(footprint_pages=10_000, accesses=64, stride=2)
    p0, _ = _emit(gen, seed=0, interval=0)
    p1, _ = _emit(gen, seed=0, interval=1)
    assert p0[0] == 0 and p1[0] == (64 * 2) % 10_000  # picks up where 0 left
    np.testing.assert_array_equal(np.diff(p0) % 10_000, 2)


def test_pointer_chase_matches_stepped_lcg():
    """The closed-form uint32 chain == literally stepping the LCG on host."""
    gen = G.PointerChase(footprint_pages=3000, accesses=200)
    pages, _ = _emit(gen, seed=4, interval=0)
    key = G.interval_key(jnp.int32(4), jnp.int32(0))
    x = int(np.asarray(
        jax.random.bits(jax.random.fold_in(key, 19), (), jnp.uint32)
    ))
    ref = []
    for _ in range(200):
        ref.append((x >> 7) % 3000)
        x = (1664525 * x + 1013904223) % (1 << 32)
    np.testing.assert_array_equal(pages, np.asarray(ref, np.int32))


def test_mix_members_stay_in_their_superpage_lanes():
    gen = SMALL_GENERATORS[4]
    bases = gen._bases
    spans = [(-(-m.footprint_pages // PAGES_PER_SP)) * PAGES_PER_SP
             for m in gen.members]
    pages, _ = _emit(gen, seed=7, interval=0)
    for base, span, m in zip(bases, spans, gen.members):
        in_lane = (pages >= base) & (pages < base + span)
        assert in_lane.sum() >= m.accesses  # every member emitted its share
    assert gen.footprint_pages == bases[-1] + spans[-1]


# ---------------------------------------------------------------------------
# Hypothesis property layer (optional, as in tests/test_core_*)
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # pragma: no cover - exercised via the floors above
    st = None

if st is not None:

    def _gens():
        zipf = st.builds(
            G.ZipfHotspot,
            footprint_pages=st.integers(64, 4096),
            accesses=st.integers(32, 1024),
            hot_frac=st.floats(0.01, 1.0),
            zipf_alpha=st.floats(0.3, 2.0),
            hot_traffic=st.floats(0.0, 1.0),
            write_ratio=st.floats(0.0, 1.0),
        )
        phase = st.builds(
            G.PhaseShift,
            footprint_pages=st.integers(64, 4096),
            accesses=st.integers(32, 1024),
            ws_frac=st.floats(0.05, 1.0),
            drift_frac=st.floats(0.0, 1.0),
            hot_frac=st.floats(0.01, 1.0),
            zipf_alpha=st.floats(0.3, 2.0),
            hot_traffic=st.floats(0.0, 1.0),
            write_ratio=st.floats(0.0, 1.0),
        )
        seq = st.builds(
            G.SequentialScan,
            footprint_pages=st.integers(64, 4096),
            accesses=st.integers(32, 1024),
            stride=st.integers(1, 9),
            write_ratio=st.floats(0.0, 1.0),
        )
        chase = st.builds(
            G.PointerChase,
            footprint_pages=st.integers(64, 4096),
            accesses=st.integers(32, 1024),
            write_ratio=st.floats(0.0, 1.0),
        )
        leaf = st.one_of(zipf, phase, seq, chase)
        mix = st.builds(
            lambda ms: G.InterleavedMix(members=tuple(ms)),
            st.lists(leaf, min_size=1, max_size=3),
        )
        return st.one_of(leaf, mix)

    @settings(max_examples=20, deadline=None)
    @given(_gens(), st.integers(0, 2**31 - 1), st.integers(0, 50))
    def test_generator_properties(gen, seed, interval):
        check_generator_invariants(gen, seed, interval)

    @settings(max_examples=10, deadline=None)
    @given(_gens(), st.integers(0, 2**31 - 1), st.integers(0, 50))
    def test_generator_determinism_property(gen, seed, interval):
        check_generator_determinism(gen, seed, interval)

else:

    @pytest.mark.skip(reason="hypothesis not installed")
    def test_generator_properties():
        pass

    @pytest.mark.skip(reason="hypothesis not installed")
    def test_generator_determinism_property():
        pass


# ---------------------------------------------------------------------------
# Registry + probe_meta dispatch
# ---------------------------------------------------------------------------


def test_registry_covers_app_profiles_and_stressors():
    names = S.available_scenarios()
    from repro.sim.config import APPS

    assert {f"syn/{a}" for a in APPS} <= set(names)  # all 14 paper profiles
    assert {"stress/zipf-hotspot", "stress/phase-shift", "stress/seq-scan",
            "stress/pointer-chase", "stress/mix"} <= set(names)


def test_registry_rejects_duplicates_and_shadows():
    sc = S.get_scenario("stress/seq-scan")
    with pytest.raises(ValueError, match="already registered"):
        S.register_scenario(sc)
    with pytest.raises(ValueError, match="shadows"):
        S.register_scenario(dataclasses.replace(sc, name="streamcluster"))
    with pytest.raises(KeyError, match="unknown scenario"):
        S.get_scenario("nope/missing")


def test_probe_meta_dispatches_and_matches_materialized_shapes():
    """trace.probe_meta must report EXACTLY what the generator emits — the
    compile-signature contract fleet grouping rests on (satellite fix)."""
    for name in ("stress/mix", "syn/soplex"):
        for accesses in (None, 640):
            meta = trace_mod.probe_meta(name, accesses)
            tr = trace_mod.generate(name, seed=1, interval=0, accesses=accesses)
            assert meta["footprint_pages"] == tr.footprint_pages
            assert meta["num_superpages"] == tr.num_superpages
            assert meta["accesses_per_interval"] == tr.sp.shape[0]
            assert meta["inst_per_access"] == tr.inst_per_access
            assert tr.vpn.max() < meta["footprint_pages"]
    with pytest.raises(KeyError):
        trace_mod.probe_meta("not-a-workload")


def test_fused_spec_shape_mismatch_fails_loudly():
    spec = simloop.EngineSpec(
        policy="flat-static", mc=MachineConfig(), num_superpages=1,
        footprint_pages=999,  # wrong on purpose
        source=simloop.TraceSource("stress/seq-scan", 500),
    )
    with pytest.raises(ValueError, match="shape mismatch"):
        simloop.engine_run_fused(spec, simloop.engine_init(spec), 0, 1)
    staged = dataclasses.replace(spec, source=None)
    with pytest.raises(ValueError, match="staged compile"):
        simloop.batch_run_fused(staged, 1)


# ---------------------------------------------------------------------------
# The differential gate: fused in-scan generation == staged materialization
# ---------------------------------------------------------------------------


def _metrics_tuple(m):
    return (m.ipc, m.total_cycles, m.mpki, m.migrations, m.evictions,
            m.shootdowns, m.mig_bytes, tuple(sorted(m.breakdown.items())))


@pytest.mark.parametrize("name", S.available_scenarios())
def test_every_preset_fused_matches_staged(name):
    """EVERY registered preset: staged oracle == fused path, bitwise."""
    staged = simulate(name, "flat-static", intervals=INTERVALS,
                      accesses=ACCESSES, seed=3)
    fused = simulate(name, "flat-static", intervals=INTERVALS,
                     accesses=ACCESSES, seed=3, fused=True)
    assert _metrics_tuple(staged) == _metrics_tuple(fused)


@pytest.mark.parametrize("policy", ["rainbow", "hscc-4kb-mig", "hscc-2mb-mig",
                                    "flat-static", "dram-only"])
def test_all_policies_fused_match_staged(policy):
    """One scenario across ALL five policy programs (stateful included)."""
    staged = simulate("stress/phase-shift", policy, intervals=INTERVALS,
                      accesses=ACCESSES, seed=9)
    fused = simulate("stress/phase-shift", policy, intervals=INTERVALS,
                     accesses=ACCESSES, seed=9, fused=True)
    assert _metrics_tuple(staged) == _metrics_tuple(fused)


def test_fused_vmap_over_seeds_matches_per_seed():
    """engine_run_fused_batch == stacked per-seed engine_run_fused, bitwise."""
    name, seeds = "stress/zipf-hotspot", [0, 1, 2]
    meta = trace_mod.probe_meta(name, ACCESSES)
    spec = simloop.EngineSpec(
        policy="rainbow", mc=MachineConfig(),
        num_superpages=meta["num_superpages"],
        footprint_pages=meta["footprint_pages"],
        source=simloop.TraceSource(name, ACCESSES),
    )
    state0 = simloop.engine_init(spec)
    states = jax.tree.map(lambda x: jnp.stack([x] * len(seeds)), state0)
    finals_b, stats_b = simloop.engine_run_fused_batch(
        spec, states, jnp.asarray(seeds, jnp.int32), INTERVALS
    )
    for i, seed in enumerate(seeds):
        finals_1, stats_1 = simloop.engine_run_fused(
            spec, state0, seed, INTERVALS
        )
        for b, one in zip(stats_b, stats_1):
            np.testing.assert_array_equal(np.asarray(b)[i], np.asarray(one))
        for b, one in zip(finals_b.sim.counters, finals_1.sim.counters):
            np.testing.assert_array_equal(np.asarray(b)[i], np.asarray(one))


# ---------------------------------------------------------------------------
# Fleet integration: grouping, staging, and the 4-device sharded fleet
# ---------------------------------------------------------------------------


def test_grid_rejects_lopsided_axes():
    """Workloads without policies/seeds (or vice versa) would silently build
    an EMPTY plan; grid must reject the combination loudly instead."""
    with pytest.raises(ValueError, match="ZERO cells"):
        fleet.SweepPlan.grid(scenario=["stress/mix"], seeds=(0, 1))
    with pytest.raises(ValueError, match="ZERO cells"):
        fleet.SweepPlan.grid(policies=["rainbow"])
    with pytest.raises(ValueError, match="ZERO cells"):
        fleet.SweepPlan.grid(apps=["soplex"], policies=["rainbow"], seeds=())
    assert len(fleet.SweepPlan.grid()) == 0  # explicitly empty stays legal


def test_app_presets_keep_exact_hot_page_counts():
    """syn/<app> hot-set sizes must round-trip the Table-I integer count
    through ZipfHotspot.hot_frac without losing a page to f64 truncation."""
    from repro.sim.config import APPS
    from repro.sim.trace import _mb_to_pages

    for app, prof in APPS.items():
        gen = S.get_scenario(f"syn/{app}").gen
        fp = _mb_to_pages(prof.footprint_mb)
        ws = min(_mb_to_pages(prof.working_set_mb), fp)
        want = max(1, int(ws * prof.hot_page_pct / 100.0))
        assert gen._n_hot == want, (app, gen._n_hot, want)


def test_bucket_sampler_respects_quotas():
    """sp_hot_buckets (Table II): every superpage's hot-page count stays
    within its sampled bucket's [lo, hi] cap, the hot set is unique and
    in-range, and the same seed reproduces the same set bitwise."""
    gen = G.ZipfHotspot(
        footprint_pages=16 * PAGES_PER_SP, accesses=1000, hot_frac=0.01,
        sp_hot_buckets=((1.0, 2, 6), (1.0, 8, 12)),
    )
    gen.validate()
    hot = np.asarray(gen.setup(jnp.int32(5)))
    assert hot.shape == (gen._n_hot,)
    assert len(np.unique(hot)) == hot.shape[0]
    assert hot.min() >= 0 and hot.max() < gen.footprint_pages
    per_sp = np.bincount(hot // PAGES_PER_SP, minlength=16)
    # quotas cap per-superpage counts at the widest bucket's hi
    assert per_sp.max() <= 12, per_sp
    assert np.array_equal(hot, np.asarray(gen.setup(jnp.int32(5))))
    assert not np.array_equal(hot, np.asarray(gen.setup(jnp.int32(6))))


def test_bucket_validation_rejects_malformed_entries():
    base = dict(footprint_pages=PAGES_PER_SP, accesses=100)
    for bad in (
        ((1.0, 2),),  # not a 3-tuple
        ((-1.0, 1, 4),),  # negative weight
        ((1.0, 0, 4),),  # lo < 1
        ((1.0, 5, 4),),  # lo > hi
        ((1.0, 1, PAGES_PER_SP + 1),),  # hi past the superpage
        ((0.0, 1, 4),),  # all weights zero
    ):
        with pytest.raises(ValueError):
            G.ZipfHotspot(sp_hot_buckets=bad, **base).validate()


def test_plan_groups_fused_cells():
    """Fused cells group per scenario program (spec.source in the signature);
    fused and staged modes of one scenario never share a compile."""
    plan = fleet.SweepPlan.grid(
        apps=["stress/seq-scan"], policies=["rainbow"], seeds=(0, 1),
        scenario=["stress/seq-scan", "stress/pointer-chase"],
        intervals=2, accesses=900,
    )
    groups = fleet.plan_groups(plan)
    assert len(groups) == 3  # staged seq, fused seq, fused chase
    by_source = {g.spec.source: g for g in groups}
    assert None in by_source  # the staged oracle cells
    fused_seq = by_source[simloop.TraceSource("stress/seq-scan", 900)]
    assert len(fused_seq.cells) == 2  # seeds fuse on one fleet axis
    assert fused_seq.meta == by_source[None].meta  # same compile metadata
    for g in groups:
        assert all(c.fused == (g.spec.source is not None) for c in g.cells)


def test_fleet_fused_matches_staged_and_single():
    plan = fleet.SweepPlan.grid(
        apps=["stress/zipf-hotspot"], policies=["rainbow"], seeds=(0, 1),
        scenario=["stress/zipf-hotspot"], intervals=2, accesses=1500,
    )
    res = fleet.FleetRunner().run(plan)
    assert len(res) == 4
    for seed in (0, 1):
        staged = res.one(seed=seed, fused=False)
        fused = res.one(seed=seed, fused=True)
        single = simulate("stress/zipf-hotspot", "rainbow", intervals=2,
                          accesses=1500, seed=seed)
        assert _metrics_tuple(staged) == _metrics_tuple(fused) \
            == _metrics_tuple(single)


def test_sharded_fused_fleet_bit_identical_on_4_devices():
    """4 forced host devices: the fused shard_map fleet == staged fleet ==
    single-device engine, including the padding path (3 cells on 4 devs)."""
    script = textwrap.dedent("""
        import jax
        import numpy as np
        from repro.engine import fleet
        from repro.sim.runner import simulate, sweep

        assert len(jax.devices()) == 4
        plan = fleet.SweepPlan.grid(
            apps=["stress/mix"], policies=["rainbow"], seeds=(0, 1, 2),
            scenario=["stress/mix"], intervals=2, accesses=1800,
        )  # 3 cells per group: NOT divisible by 4 devices
        runner = fleet.FleetRunner()
        fused_groups = [g for g in fleet.plan_groups(plan)
                        if g.spec.source is not None]
        (fg,) = fused_groups
        states, seeds = runner._stage(fg)
        assert seeds.shape == (4,) and seeds.dtype == np.int32  # padded 3->4
        assert len(seeds.sharding.device_set) == 4, seeds.sharding

        res = runner.run(plan)
        for seed in (0, 1, 2):
            staged = res.one(seed=seed, fused=False)
            fused = res.one(seed=seed, fused=True)
            one = simulate("stress/mix", "rainbow", intervals=2,
                           accesses=1800, seed=seed)
            assert staged.ipc == fused.ipc == one.ipc
            assert staged.total_cycles == fused.total_cycles == one.total_cycles
            assert staged.migrations == fused.migrations == one.migrations
            assert staged.mig_bytes == fused.mig_bytes == one.mig_bytes
        out = sweep([], ["rainbow"], [1], intervals=2, accesses=1800,
                    scenarios=["stress/mix"])
        assert out[("stress/mix", "rainbow", 1)].ipc == res.one(
            seed=1, fused=True).ipc
        print("WORKLOADS_SHARDED_OK")
    """)
    env = dict(os.environ, PYTHONPATH="src",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        env=env, cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=600,
    )
    assert "WORKLOADS_SHARDED_OK" in out.stdout, out.stderr[-2000:]


def test_calibration_mode_works_on_scenarios():
    """Scenario cells flow through the host-only calibration path too."""
    plan = fleet.SweepPlan.grid(
        apps=["stress/zipf-hotspot"], policies=["rainbow"], seeds=(1,),
        intervals=1, accesses=2000,
    )
    stats = fleet.FleetRunner().calibration(plan)[plan.cells[0]]
    assert stats["working_set_pages"] > 0
    assert 0 < stats["hot_page_pct_measured"] <= 100
