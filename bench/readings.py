"""Readings that set a cell's limits: the program's and the control's numbers.

    python3 bench/readings.py --workload <cell> --seeds <n> <n> ... [--control-only]

For each seed, prints one JSON line with the numbers the cell's check
compares: those of the program (one unit of the timed path, at the cell's
own size, checked as a run checks it) and those of the control, the
reference computed a precision below the configuration's and put in the
program's place. The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-only", action="store_true",
                    help="read only the control (the simulator's control needs no program)")
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".jax_cache"))
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from bench import harness

    bench = harness.Bench(ROOT)
    cell = bench.cell(args.workload)
    cfg, mix = bench.config(cell["config"]), bench.traffic(cell["traffic"])
    devs = harness.devices_for(int(cell["chips"]), True)
    module = bench.driver(mix["driver"])
    for line in module.readings(cfg, mix, args.seeds, devs, bench.reference(cfg["reference"]),
                                program=not args.control_only):
        line["t"] = time.time()
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
