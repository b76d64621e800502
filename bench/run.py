"""One benchmark run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints, as the last line of standard output, one JSON object: correct,
attempted, failed, metrics (the cell's end-to-end metrics, or with --trace 1
its per-layer metrics), device, with --trace 1 breakdown, and last the
numbers the correctness check compared, each with its limit; those are also
the last lines of standard error. Exits non-zero, with no result, where JAX
finds no TPU or fewer chips than the cell needs.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # One persistent compile cache at a fixed path inside the checkout,
    # unless the environment names one; set before JAX reads its config.
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".jax_cache"))
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from bench import harness

    try:
        line = harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                                bool(args.trace), t_start=T_START)
    except harness.NoChip as e:
        print(f"refused: {e}", file=sys.stderr)
        return 3
    print(json.dumps(line), flush=True)
    for name, c in line["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
