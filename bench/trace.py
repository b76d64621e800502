"""Reduction of a profiler trace (`.xplane.pb`) to device busy and idle time.

Device planes are those whose name starts with "/device:" (one per chip).
On each, the operations are the events of the "XLA Ops" line; where a plane
has no such line, its "XLA Modules" line stands in. Busy time is the union
of the operations' intervals inside the window, which is the host span
"bench.window" that the harness puts around the measured units; it is
averaged over the chips. Each idle gap of the first chip is labelled with
the innermost host event on the harness's thread that covers the gap's
middle: what the host was doing while the chip waited.

A trace taken on the CPU backend has no device plane; there the XLA
operations that the host's XLA threads ran stand in for one device, so the
same reduction runs in tests without a chip.
"""
from __future__ import annotations

import collections
import pathlib

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:"


def load(log_dir):
    """ProfileData of the one .xplane.pb under `log_dir`."""
    import jax

    found = sorted(pathlib.Path(log_dir).rglob("*.xplane.pb"))
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, found {found}")
    return jax.profiler.ProfileData.from_file(str(found[0]))


def _events(line):
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events]


CPU_PLANE = "/host:CPU"
CPU_XLA_LINE = "tf_XLA"
CPU_MARKERS = ("ThreadpoolListener", "end: ", "SlinkyThreadPool", "ThunkExecutor")


def device_ops(pd, platform: str = "tpu") -> dict[str, list]:
    """{device plane: [(op name, start_ns, end_ns)]}."""
    out = {}
    if platform == "cpu":
        for plane in pd.planes:
            if plane.name == CPU_PLANE:
                out[plane.name] = [
                    ev for ln in plane.lines if ln.name.startswith(CPU_XLA_LINE)
                    for ev in _events(ln) if ev[2] > ev[1] and not ev[0].startswith(CPU_MARKERS)]
        return out
    for plane in pd.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        line = lines.get(OPS_LINE) or lines.get(MODULES_LINE)
        if line is not None:
            out[plane.name] = _events(line)
    return out


def host_thread(pd, span: str):
    """(events of the host line that holds `span`, the span's interval)."""
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            continue
        for line in plane.lines:
            if line.name.startswith(CPU_XLA_LINE):
                continue
            evs = _events(line)
            for name, s, e in evs:
                if name == span:
                    return evs, (s, e)
    raise KeyError(f"no host span {span!r} in the trace")


def merge(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Union of intervals clipped to [lo, hi], as sorted disjoint pieces."""
    out: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def reduce(pd, platform: str = "tpu", span_prefix: str = "bench.",
           window_span: str = "bench.window", top: int = 10) -> dict:
    """busy_s, window_s, device_ops and idle_gaps of the traced window."""
    host, (w0, w1) = host_thread(pd, window_span)
    planes = device_ops(pd, platform)
    if not planes:
        raise ValueError("the trace holds no device plane")
    busy = []
    per_op: collections.Counter = collections.Counter()
    for evs in planes.values():
        busy.append(sum(e - s for s, e in merge([(s, e) for _, s, e in evs], w0, w1)))
        for name, s, e in evs:
            if e > w0 and s < w1:
                per_op[name] += min(e, w1) - max(s, w0)
    n = len(planes)
    first = next(iter(planes.values()))
    pieces = merge([(s, e) for _, s, e in first], w0, w1)
    gaps, t = [], w0
    for s, e in pieces + [(w1, w1)]:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    labelled = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = (s + e) / 2
        covering = [(ee - ss, name) for name, ss, ee in host
                    if ss <= mid <= ee and name != window_span]
        label = min(covering)[1] if covering else window_span
        labelled.append([label, (e - s) * 1e-9])
    return {
        "busy_s": sum(busy) / n * 1e-9,
        "window_s": (w1 - w0) * 1e-9,
        "chips": n,
        "device_ops": [[k, v / n * 1e-9] for k, v in per_op.most_common(top)],
        "idle_gaps": labelled,
        "spans": collections.Counter(name for name, _, _ in host if name.startswith(span_prefix)),
    }
