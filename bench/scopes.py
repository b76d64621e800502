"""Device time by named scope, read from a profiler trace (`.xplane.pb`).

On the chip the metadata of each operation's event holds the op's HLO
op_name (the stat "tf_op", as "<op_name>:"): the path of `jax.named_scope`s
(and jit, while/body, ...) that produced it, ending in the primitive. An
op's scope path is its op_name less that last segment. `jax.profiler.
ProfileData` does not expose event metadata, so this module reads the
device planes from the file itself and joins each event to its metadata by
id. Ops that XLA adds (copies of buffers it may not alias, for one) carry no
op_name; on the CPU backend no op does, and every map here is empty.

Over the window that `bench.trace` reduces (the host span "bench.window"):

- `scopes`: {scope path: {"self_s", "gap_before_s"}}, averaged over the
  chips. An op's self time is its time less the events nested inside it on
  the same line (a `while` less its body). Each idle gap goes to the op that
  starts where it ends.
- `shares`: the per-phase shares of `SHARES`, read from `scopes`.
- `idle_by_span`: idle seconds by the innermost program span (`serve.*`,
  `sim.*`) on any host thread that covers each gap's middle.

    python3 -m bench.scopes <trace dir>
    python3 -m bench.scopes --workload <cell> --seed <n> --seconds <s>

Run from the repo root. The first form reduces a trace taken by hand: the
program under `jax.profiler.trace(dir)`, with a
`jax.profiler.TraceAnnotation("bench.window")` around the part to read. The
second makes one traced run of a benchmark cell, as `bench/run.py --trace 1`
does, and reduces its trace before the harness deletes it. Each prints the
reduction as one JSON line, the second after the run's own line.
"""
from __future__ import annotations

import argparse
import bisect
import collections
import json
import pathlib
import sys

from bench import trace

#: The event-metadata stat that holds an operation's HLO op_name.
OP_NAME_STAT = "tf_op"
#: Prefixes of the program's own host spans (launch.serve, sim.runner).
PROGRAM_SPANS = ("serve.", "sim.")

#: Named scopes that only the simulation program carries (engine.simloop,
#: sim.tlbsim, core.rainbow, ...); its "observe" is the decode step's too.
SIM_SCOPES = frozenset({"synth", "tlb", "tlb4k", "tlb2m", "bmc", "plan", "apply", "queue"})
#: Named scopes that only the paged decode step carries (serving.rainbow_decode).
DECODE_SCOPES = frozenset({"translate", "layers", "qkv", "read", "attend", "mlp", "append",
                           "promote", "logits"})
DECLARED = SIM_SCOPES | DECODE_SCOPES | {"observe"}

#: share name: (what it divides by, the scopes it sums, the program it reads).
#: "window" shares count self time plus the gap before each op, as % of the
#: traced window: meant for a slice inside one device program, whose gaps
#: are the program's own. "busy" shares count self time, as % of busy time.
SHARES = {
    "sim.tlb4k_share": ("window", ("tlb4k",), SIM_SCOPES),
    "sim.tlb2m_share": ("window", ("tlb2m",), SIM_SCOPES),
    "sim.bmc_share": ("window", ("bmc",), SIM_SCOPES),
    "decode.attend_share": ("busy", ("read", "attend"), DECODE_SCOPES),
    "decode.append_share": ("busy", ("append",), DECODE_SCOPES),
    "decode.control_share": ("busy", ("observe", "promote"), DECODE_SCOPES),
}


def under(path: str, names) -> bool:
    """Whether one of the "/" segments of a scope path is in `names`."""
    return not set(path.split("/")).isdisjoint(names)


def _scoped(red: dict, program) -> dict | None:
    scopes = red.get("scopes") or {}
    if not any(under(path, program) for path in scopes):
        return None
    return scopes


def window_share(red: dict, names, program=SIM_SCOPES) -> float | None:
    """% of the traced window in ops under `names`: self time plus the idle
    gap before each. None where no op carries a scope of `program`."""
    scopes = _scoped(red, program)
    if scopes is None or red["window_s"] <= 0:
        return None
    t = sum(v["self_s"] + v["gap_before_s"] for p, v in scopes.items() if under(p, names))
    return 100.0 * t / red["window_s"]


def busy_share(red: dict, names, program=DECODE_SCOPES) -> float | None:
    """% of the device's busy time in the self time of ops under `names`.
    None where no op carries a scope of `program`."""
    scopes = _scoped(red, program)
    if scopes is None or red["busy_s"] <= 0:
        return None
    t = sum(v["self_s"] for p, v in scopes.items() if under(p, names))
    return 100.0 * t / red["busy_s"]


def shares(red: dict) -> dict[str, float]:
    """The shares of `SHARES` that the reduction `red` holds."""
    out = {}
    for name, (base, names, program) in SHARES.items():
        value = (window_share if base == "window" else busy_share)(red, names, program)
        if value is not None:
            out[name] = value
    return out


def _xspace_class():
    """A message class for the part of XSpace (tsl/profiler/protobuf/
    xplane.proto) that holds the device ops and their metadata. The fields
    it leaves out are skipped."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    f = descriptor_pb2.FileDescriptorProto(name="bench_xspace_ops.proto",
                                           package="bench_xspace", syntax="proto3")
    fd = descriptor_pb2.FieldDescriptorProto
    one, rep = fd.LABEL_OPTIONAL, fd.LABEL_REPEATED
    i64, text = fd.TYPE_INT64, fd.TYPE_STRING
    for name, fields in {
        "XStat": [("metadata_id", 1, i64, one), ("str_value", 5, text, one)],
        "XEvent": [("metadata_id", 1, i64, one), ("offset_ps", 2, i64, one),
                   ("duration_ps", 3, i64, one)],
        "XLine": [("name", 2, text, one), ("timestamp_ns", 3, i64, one),
                  ("events", 4, "XEvent", rep)],
        "XEventMetadata": [("name", 2, text, one), ("stats", 5, "XStat", rep)],
        "XStatMetadata": [("name", 2, text, one)],
        "EventMetadataEntry": [("key", 1, i64, one), ("value", 2, "XEventMetadata", one)],
        "StatMetadataEntry": [("key", 1, i64, one), ("value", 2, "XStatMetadata", one)],
        "XPlane": [("name", 2, text, one), ("lines", 3, "XLine", rep),
                   ("event_metadata", 4, "EventMetadataEntry", rep),
                   ("stat_metadata", 5, "StatMetadataEntry", rep)],
        "XSpace": [("planes", 1, "XPlane", rep)],
    }.items():
        m = f.message_type.add(name=name)
        for fname, number, kind, label in fields:
            field = m.field.add(name=fname, number=number, label=label,
                                type=fd.TYPE_MESSAGE if isinstance(kind, str) else kind)
            if isinstance(kind, str):
                field.type_name = f".bench_xspace.{kind}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(pool.FindMessageTypeByName("bench_xspace.XSpace"))


def _scope_path(op_name: str | None) -> str | None:
    """An op's scope path: its op_name less the last segment (the primitive)."""
    if op_name is None:
        return None
    return op_name.rsplit("/", 1)[0] if "/" in op_name else ""


def device_events(raw: bytes) -> dict[str, list[tuple[str | None, str, float, float]]]:
    """{device plane: [(scope path or None, op name, start ns, end ns)]}: the
    events of each device plane's ops line (the line `bench.trace` reads),
    each joined to its own event metadata by id."""
    space = _xspace_class()()
    space.ParseFromString(raw)
    out = {}
    for plane in space.planes:
        if not plane.name.startswith(trace.DEVICE_PREFIX):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        line = lines.get(trace.OPS_LINE) or lines.get(trace.MODULES_LINE)
        if line is None:
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        meta = {}
        for entry in plane.event_metadata:
            op_name = None
            for st in entry.value.stats:
                if stat_names.get(st.metadata_id) == OP_NAME_STAT:
                    op_name = st.str_value.removesuffix(":") or None
            meta[entry.key] = (_scope_path(op_name), entry.value.name.split(" = ")[0])
        # whole ns, cut down as ProfileData cuts them, so that the ops add
        # up to the busy time that `bench.trace` reads from ProfileData
        t0 = line.timestamp_ns
        out[plane.name] = [(*meta.get(e.metadata_id, (None, "")), s, s + e.duration_ps // 1000)
                           for e in line.events for s in (t0 + e.offset_ps // 1000,)]
    return out


def _gaps(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The idle gaps of [lo, hi] outside the union of `intervals`."""
    gaps, t = [], lo
    for s, e in trace.merge(intervals, lo, hi) + [(hi, hi)]:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    return gaps


def op_times(events, lo: float, hi: float) -> list[tuple[str | None, str, float, float]]:
    """[(scope path, op name, self ns, idle ns just before)] of one device line.

    Events are clipped to [lo, hi]. An event that lies inside an earlier one
    is nested in it: its time leaves the enclosing event's self time. Each
    idle gap of the line's union goes to the first event that starts where
    the gap ends (the outermost, where several start together); idle time
    after the last event goes to none."""
    evs = sorted(((max(s, lo), min(e, hi), path, name) for path, name, s, e in events
                  if min(e, hi) > max(s, lo)), key=lambda ev: (ev[0], -ev[1]))
    selfs = [e - s for s, e, _, _ in evs]
    stack: list[int] = []  # enclosing events, innermost last
    for i, (s, e, _, _) in enumerate(evs):
        while stack and evs[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            selfs[stack[-1]] -= min(e, evs[stack[-1]][1]) - s
        stack.append(i)
    before = [0.0] * len(evs)
    starts = [s for s, _, _, _ in evs]
    for g0, g1 in _gaps([(s, e) for s, e, _, _ in evs], lo, hi):
        i = bisect.bisect_left(starts, g1)
        if i < len(evs):
            before[i] += g1 - g0
    return [(path, name, t, g) for (_, _, path, name), t, g in zip(evs, selfs, before)]


def reduce(pd, raw: bytes, window_span: str = "bench.window", top: int = 10) -> dict:
    """window_s, busy_s, scopes, shares, the share of busy time in ops under
    a declared scope, the `top` ops under none, and idle_by_span."""
    platform = "tpu" if any(p.name.startswith(trace.DEVICE_PREFIX) for p in pd.planes) else "cpu"
    red = trace.reduce(pd, platform, window_span=window_span)
    _, (w0, w1) = trace.host_thread(pd, window_span)
    planes = device_events(raw)
    n = max(len(planes), 1)
    total: dict[str, list[float]] = collections.defaultdict(lambda: [0.0, 0.0])
    unscoped: collections.Counter = collections.Counter()
    for evs in planes.values():
        for path, name, self_ns, gap_ns in op_times(evs, w0, w1):
            if path is not None:
                total[path][0] += self_ns
                total[path][1] += gap_ns
            if path is None or not under(path, DECLARED):
                unscoped[f"{name} [{path or ''}]"] += self_ns
    out = {"window_s": red["window_s"], "busy_s": red["busy_s"],
           "scopes": {path: {"self_s": v[0] / n * 1e-9, "gap_before_s": v[1] / n * 1e-9}
                      for path, v in sorted(total.items())}}
    out["shares"] = shares(out)
    if out["scopes"] and out["busy_s"] > 0:
        out["declared_busy_share"] = 100.0 * sum(
            v["self_s"] for p, v in out["scopes"].items() if under(p, DECLARED)) / out["busy_s"]
        out["undeclared_ops"] = [[k, 100.0 * v / n * 1e-9 / out["busy_s"]]
                                 for k, v in unscoped.most_common(top)]
    out["idle_by_span"] = idle_by_span(pd, platform, window_span)
    return out


def program_spans(pd) -> list[tuple[str, float, float]]:
    """The program's own host spans (`PROGRAM_SPANS`) on every host thread."""
    out = []
    for plane in pd.planes:
        if plane.name.startswith(trace.DEVICE_PREFIX):
            continue
        for line in plane.lines:
            if line.name.startswith(trace.CPU_XLA_LINE):
                continue
            out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events
                       if e.name.startswith(PROGRAM_SPANS))
    return out


def idle_by_span(pd, platform: str = "tpu", window_span: str = "bench.window") -> dict:
    """{program span name: idle seconds} of the first chip inside the window.

    Each idle gap goes to the innermost program span, on any host thread,
    that covers its middle; "-" holds the gaps that none covers."""
    _, (w0, w1) = trace.host_thread(pd, window_span)
    planes = trace.device_ops(pd, platform)
    if not planes:
        raise ValueError("the trace holds no device plane")
    first = next(iter(planes.values()))
    gaps = _gaps([(s, e) for _, s, e in first], w0, w1)
    mids = [(s + e) / 2 for s, e in gaps]
    label = ["-"] * len(gaps)
    for name, s, e in sorted(program_spans(pd), key=lambda sp: sp[1] - sp[2]):
        for i in range(bisect.bisect_left(mids, s), bisect.bisect_right(mids, e)):
            label[i] = name
    out: collections.Counter = collections.Counter()
    for name, (s, e) in zip(label, gaps):
        out[name] += (e - s) * 1e-9
    return dict(out.most_common())


def read(log_dir) -> tuple[object, dict]:
    """(ProfileData, reduction) of the one .xplane.pb under `log_dir`."""
    import jax

    found = sorted(pathlib.Path(log_dir).rglob("*.xplane.pb"))
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, found {found}")
    raw = found[0].read_bytes()
    pd = jax.profiler.ProfileData.from_serialized_xspace(raw)
    return pd, reduce(pd, raw)


def traced_run(workload: str, seed: int, seconds: float) -> int:
    """One `bench/run.py --trace 1` run of `workload`; prints its line, then
    the reduction of its trace, which `bench.trace.load` hands over on its
    way to the harness."""
    from bench import run  # the run's set-up clock starts here

    found: dict = {}

    def load(log_dir):
        try:
            pd, found["reduction"] = read(log_dir)
        except Exception as e:  # the run's own line still prints
            found["error"] = repr(e)
            return trace_load(log_dir)
        return pd

    trace_load, trace.load = trace.load, load
    rc = run.main(["--workload", workload, "--seed", str(seed), "--seconds", str(seconds),
                   "--trace", "1"])
    print(json.dumps(found), flush=True)
    return rc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir", nargs="?", help="a directory holding one .xplane.pb")
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int)
    ap.add_argument("--seconds", type=float)
    args = ap.parse_args(argv)
    if args.trace_dir is not None:
        print(json.dumps(read(args.trace_dir)[1]), flush=True)
        return 0
    if None in (args.workload, args.seed, args.seconds):
        ap.error("give a trace directory, or --workload, --seed and --seconds")
    return traced_run(args.workload, args.seed, args.seconds)


if __name__ == "__main__":
    sys.exit(main())
