"""The benchmark harness: one run of one cell, driven by files found by name.

`BENCHMARK.json` names each cell's configuration and traffic mix. Everything
else is a file of its own under `bench/`:

  configs/<config>.json     the configuration as it is run (named in
                            BENCHMARK.json), with its plain reference
  traffic/<traffic>.json    the mix's parameters; its "driver" key names
  drivers/<driver>.py       the code that sets the mix up on the program,
                            runs one unit of work and checks what it produced
  metrics/<metric>.py       one reader per per-layer metric

so a later change adds a cell, a configuration, a mix or a metric by adding
files and entries, never by editing one that is there.

A run: set-up (imports, data and weights from the seed, warm-up of every
shape the cell uses) is `setup_s`; then whole units of work run back to back
until `seconds` have passed, finishing the unit in flight, and a rate is all
the work of the window over all of its time. With `trace=True` the window
runs under the profiler and the per-layer metrics are read from its trace;
a mix whose units are single device programs too long to trace whole names
a slice instead ("trace_slice_at_s", "trace_slice_s"): the profiler then
records that many seconds, that far into the window.
After the window the peak memory is read, the program's state is freed, and
the cell's driver module compares a sample of what the window produced with
the reference.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
import re
import shutil
import sys
import tempfile
import threading
import time
from typing import Any

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
BENCH_DIR = "bench"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_module(path: pathlib.Path, name: str):
    """Import a file by path (file names may hold '.' and '-')."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Bench:
    """BENCHMARK.json of one checkout and the files it names."""

    def __init__(self, root: str | pathlib.Path):
        self.root = pathlib.Path(root).resolve()
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())
        self.dir = self.root / BENCH_DIR

    def _named(self, key: str, name: str) -> dict:
        for entry in self.spec[key]:
            if entry["name"] == name:
                return entry
        raise KeyError(f"no {key} entry named {name!r} in BENCHMARK.json; "
                       f"known: {[e['name'] for e in self.spec[key]]}")

    def cell(self, name: str) -> dict:
        return self._named("workloads", name)

    def config(self, name: str) -> dict:
        return json.loads((self.root / self._named("configs", name)["file"]).read_text())

    def traffic(self, name: str) -> dict:
        return json.loads((self.dir / "traffic" / f"{_checked(name)}.json").read_text())

    def driver(self, name: str):
        return load_module(self.dir / "drivers" / f"{_checked(name)}.py", f"bench_driver_{name}")

    def reader(self, metric: str):
        return load_module(self.dir / "metrics" / f"{_checked(metric)}.py", f"bench_metric_{metric}")

    def reference(self, path: str):
        return load_module(self.root / path, "bench_reference_" + pathlib.Path(path).stem)

    def _for_cell(self, key: str, cell: str, reported: set[str] | None) -> list[dict]:
        out = []
        for m in self.spec[key]:
            if "workloads" in m:
                if cell in m["workloads"]:
                    out.append(m)
            elif reported is None or m["moves"] in reported:
                out.append(m)
        return out

    def end_to_end(self, cell: str) -> list[dict]:
        """The cell's end-to-end metrics: setup_s and those that list it."""
        return self._for_cell("end_to_end", cell, None)

    def per_layer(self, cell: str) -> list[dict]:
        reported = {m["name"] for m in self.end_to_end(cell)}
        return self._for_cell("per_layer", cell, reported)


def _checked(name: str) -> str:
    if not NAME.match(name):
        raise ValueError(f"not a benchmark name: {name!r}")
    return name


class CompileClock:
    """Seconds of JAX tracing, lowering and compiling (cache loads included),
    the backend compile requests, and how many of those the persistent
    compile cache answered: requests less hits were compiled."""

    def __init__(self):
        import jax

        self._lock = threading.Lock()
        self.seconds = 0.0
        self.backend_compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_):
        if event.startswith("/jax/core/compile/"):
            with self._lock:
                self.seconds += duration
                if event == "/jax/core/compile/backend_compile_duration":
                    self.backend_compiles += 1

    def _on_event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits += 1

    def read(self) -> tuple[float, int, int]:
        with self._lock:
            return self.seconds, self.backend_compiles, self.cache_hits


def devices_for(chips: int, require_chip: bool):
    """The first `chips` devices; NoChip unless they are TPUs."""
    import jax

    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return devs[:chips] if len(devs) >= chips else devs


def memory_peak(devs) -> int:
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def _trace_slice(trace_dir: str, at_s: float, length_s: float, span: str) -> None:
    """Trace `length_s` seconds, `at_s` into the window, as its own window.

    The unit in flight is a device program that started before the slice,
    so this thread marks the slice with the unit's span, which labels its
    idle gaps. The Python tracer stays off: it would only see this thread
    sleep."""
    import jax

    time.sleep(at_s)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"), \
            jax.profiler.TraceAnnotation(f"bench.{span}"):
        time.sleep(length_s)
    jax.profiler.stop_trace()


def run_cell(root, workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_chip: bool = True,
             overrides: dict | None = None, log=print) -> dict:
    """One run of `workload`; returns the result line as a dict.

    `overrides` replaces keys of the configuration and the traffic mix
    ({"config": {...}, "traffic": {...}}): the tests run cells at sizes a
    CPU holds with it, and pass require_chip=False.
    """
    bench = Bench(root)
    cell = bench.cell(workload)
    cfg = {**bench.config(cell["config"]), **(overrides or {}).get("config", {})}
    mix = {**bench.traffic(cell["traffic"]), **(overrides or {}).get("traffic", {})}
    devs = devices_for(int(cell["chips"]), require_chip)
    import jax

    clock = CompileClock()
    reference = bench.reference(cfg["reference"])
    driver = bench.driver(mix["driver"]).Driver(cfg, mix, seed, devs, reference)
    setup_s = time.perf_counter() - t_start

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    slicer = None
    if trace and "trace_slice_s" in mix:
        slicer = threading.Thread(target=_trace_slice, args=(
            trace_dir, float(mix["trace_slice_at_s"]), float(mix["trace_slice_s"]),
            driver.span))
    elif trace:
        jax.profiler.start_trace(trace_dir)
    c0, n0, h0 = clock.read()
    work = 0
    ends = []  # each unit's end, seconds into the window
    t0 = time.perf_counter()
    if slicer is not None:
        slicer.start()
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            with jax.profiler.TraceAnnotation(f"bench.{driver.span}"):
                work += driver.unit()
            ends.append(time.perf_counter() - t0)
            if ends[-1] >= seconds:
                break
    window_s = time.perf_counter() - t0
    units = len(ends)
    c1, n1, h1 = clock.read()
    t_stop = time.perf_counter()
    if slicer is not None:
        slicer.join()
    elif trace:
        jax.profiler.stop_trace()
    peak = memory_peak(devs)
    counters = driver.counters()
    counters.update(window_compile_s=c1 - c0, window_backend_compiles=n1 - n0,
                    window_cache_hits=h1 - h0, window_units=units, window_work=work,
                    window_s=window_s)
    log(f"window: {units} units, {work} {mix['work_unit']} in {window_s:.3f} s; "
        f"compile/cache {c1 - c0:.3f} s, backend compile requests {n1 - n0}, "
        f"persistent-cache hits {h1 - h0}; unit seconds "
        f"{[round(b - a, 3) for a, b in zip([0.0] + ends, ends)]}", file=sys.stderr)

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result: dict[str, Any] = {}
    if trace:
        from bench import trace as tr
        from bench.peaks import peaks

        t_read = time.perf_counter()
        try:
            red = tr.reduce(tr.load(trace_dir), devs[0].platform)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"trace: stopped in {t_read - t_stop:.1f} s, reduced in "
            f"{time.perf_counter() - t_read:.1f} s", file=sys.stderr)
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        ctx = {"trace": red, "counters": counters, "config": cfg, "traffic": mix,
               "peaks": peaks(devs[0].device_kind) if require_chip else None}
        metrics = {}
        for m in bench.per_layer(workload):
            value = bench.reader(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["breakdown"] = {"device_ops": red["device_ops"][:10],
                               "idle_gaps": red["idle_gaps"][:10]}
    else:
        rates = {mix["rate_metric"]: work / window_s, "setup_s": setup_s}
        metrics = {}
        for m in bench.end_to_end(workload):
            if m["name"] not in rates:
                raise KeyError(f"cell {workload!r} reports no {m['name']!r}")
            metrics[m["name"]] = {"value": rates[m["name"]], "unit": m["unit"]}

    driver.release()
    checks, failed = driver.check()
    correct = all(c["value"] <= c["limit"] for c in checks.values()) and bool(checks)
    for name, c in checks.items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    line = {"correct": correct, "attempted": units, "failed": failed,
            "metrics": metrics, "device": device}
    line.update(result)
    line["checks"] = checks
    return line
