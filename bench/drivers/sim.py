"""Driver of the simulator cells: whole fused simulations, back to back.

One unit is one `repro.sim.runner.simulate(..., fused=True)` call at the
configuration's machine and workload; the k-th unit of the window runs seed
(base + k) mod 2**31, where base is the run's --seed. Work is simulated
accesses. The check reruns a sample of the window's simulations, drawn from
the seed, in the plain reference on the host CPU and compares every field of
the metrics.
"""
from __future__ import annotations

import numpy as np

SEED_MOD = 2**31
# Fields that count events: these must agree exactly.
COUNT_FIELDS = ("migrations", "evictions", "shootdowns", "bmc_misses", "mig_aborts")


def machine_config(cfg: dict):
    """The program's MachineConfig, built from the configuration file."""
    from repro.sim.config import MachineConfig

    ghz = cfg["cpu_ghz"]
    mig = cfg["page_bytes"] / cfg["mig_bandwidth_bytes_per_s"] * 1e9 * ghz * 2
    same = ("l1_tlb_entries", "l1_tlb_ways", "l1_tlb_lat", "l2_tlb_entries",
            "l2_tlb_ways", "l2_tlb_lat", "bitmap_cache_lat", "bitmap_cache_entries",
            "bitmap_cache_ways", "ptw_refs_4k", "ptw_refs_2m", "shootdown_cost",
            "clflush_per_line", "dram_bytes", "nvm_bytes", "dram_volt",
            "dram_read_ma", "dram_write_ma", "dram_standby_ma", "dram_refresh_ma",
            "pcm_read_pj_bit", "pcm_write_pj_bit", "line_bytes", "top_n",
            "write_weight", "mig_threshold", "t_mig_amortize")
    return MachineConfig(
        **{k: cfg[k] for k in same},
        t_dr=cfg["t_dram_read_ns"] * ghz, t_dw=cfg["t_dram_write_ns"] * ghz,
        t_nr=cfg["t_nvm_read_ns"] * ghz, t_nw=cfg["t_nvm_write_ns"] * ghz,
        remap_read_lat=cfg["t_nvm_read_ns"] * ghz,
        mig_page_cost=mig, writeback_page_cost=mig,
    )


def check_program_workload(cfg: dict, reference, accesses: int) -> None:
    """The program's registered scenario must be the configuration's workload."""
    from repro.workloads import scenarios

    sc = scenarios.get_scenario(cfg["program_scenario"])
    gen = sc.generator(accesses)
    shape = reference.workload_shape(cfg)
    want = {"footprint_pages": shape["footprint_pages"], "accesses": accesses,
            "n_hot": shape["n_hot"], "zipf_alpha": cfg["zipf_alpha"],
            "hot_traffic": cfg["hot_traffic"], "write_ratio": cfg["write_ratio"],
            "sp_hot_buckets": shape["buckets"], "inst_per_access": cfg["inst_per_access"]}
    have = {"footprint_pages": gen.footprint_pages, "accesses": gen.accesses,
            "n_hot": gen._n_hot, "zipf_alpha": gen.zipf_alpha,
            "hot_traffic": gen.hot_traffic, "write_ratio": gen.write_ratio,
            "sp_hot_buckets": tuple(gen.sp_hot_buckets), "inst_per_access": sc.inst_per_access}
    bad = {k: (have[k], want[k]) for k in want if have[k] != want[k]}
    if bad:
        raise ValueError(f"{cfg['program_scenario']} departs from the configuration: {bad}")


class Driver:
    span = "simulate"

    def __init__(self, cfg: dict, mix: dict, seed: int, devices, reference):
        from repro.sim.runner import simulate

        self.cfg, self.mix, self.ref = cfg, mix, reference
        self.accesses = int(mix.get("accesses") or cfg["accesses_per_interval"])
        self.intervals = int(mix["intervals"])
        self.seed = seed
        self.base = seed % SEED_MOD
        check_program_workload(cfg, reference, self.accesses)
        self.mc = machine_config(cfg)
        self._simulate = simulate
        self.done: list[tuple[int, dict]] = []
        self._run((self.base - 1) % SEED_MOD)  # warm-up: compiles the one program

    def _run(self, sim_seed: int) -> dict:
        m = self._simulate(self.cfg["program_scenario"], self.mix["policy"], mc=self.mc,
                           intervals=self.intervals, accesses=self.accesses,
                           seed=sim_seed, fused=True)
        return m.row()

    def unit(self) -> int:
        sim_seed = (self.base + len(self.done)) % SEED_MOD
        self.done.append((sim_seed, self._run(sim_seed)))
        return self.intervals * self.accesses

    def counters(self) -> dict:
        return {"intervals": self.intervals * len(self.done),
                "accesses": self.intervals * self.accesses * len(self.done)}

    def release(self) -> None:
        """Nothing of the program stays on the device between units."""

    def check(self) -> tuple[dict, int]:
        rng = np.random.default_rng(self.seed)
        pick = rng.choice(len(self.done), size=min(int(self.mix["check_units"]), len(self.done)),
                          replace=False)
        limits = self.mix["limits"]
        rel = counts = failed = 0
        for i in sorted(pick):
            sim_seed, got = self.done[i]
            r, c = compare(self.ref, got, reference_run(self.ref, self.cfg, self.mix, sim_seed))
            failed += int(r > limits["max_rel_gap"] or c > limits["count_mismatches"])
            rel, counts = max(rel, r), max(counts, c)
        return ({"max_rel_gap": {"value": rel, "limit": limits["max_rel_gap"]},
                 "count_mismatches": {"value": counts, "limit": limits["count_mismatches"]}},
                failed)


def reference_run(reference, cfg: dict, mix: dict, sim_seed: int, acc_dtype=None) -> dict:
    """The reference's metrics of one simulation, computed on the host CPU."""
    import jax
    import jax.numpy as jnp

    with jax.default_device(jax.devices("cpu")[0]):
        return reference.simulate(cfg, mix["policy"], sim_seed, int(mix["intervals"]),
                                  int(mix.get("accesses") or cfg["accesses_per_interval"]),
                                  acc_dtype=acc_dtype or jnp.float32)


def compare(reference, got: dict, want: dict) -> tuple[float, int]:
    """(largest relative gap over every field, count fields that differ)."""
    missing = [k for k in want if k not in got]
    if missing:
        raise KeyError(f"the compared metrics lack {missing}")
    rel = max(reference.rel_gap(float(got[k]), want[k]) for k in want)
    return rel, sum(float(got[k]) != want[k] for k in COUNT_FIELDS)


def readings(cfg: dict, mix: dict, seeds, devices, reference, program: bool = True):
    """Per seed: the program's numbers and the control's (bfloat16 counters)."""
    import jax.numpy as jnp

    drv = Driver(cfg, mix, seeds[0], devices, reference) if program else None
    for seed in seeds:
        sim_seed = seed % SEED_MOD
        want = reference_run(reference, cfg, mix, sim_seed)
        low = reference_run(reference, cfg, mix, sim_seed, jnp.bfloat16)
        line = {"seed": seed, "control": dict(zip(("max_rel_gap", "count_mismatches"),
                                                  compare(reference, low, want)))}
        if drv is not None:
            line["program"] = dict(zip(("max_rel_gap", "count_mismatches"),
                                       compare(reference, drv._run(sim_seed), want)))
        yield line
