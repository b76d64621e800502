"""The trace reduction by named scope (`bench.scopes`), on a hand-built
TPU-like trace: self time less nested ops, each idle gap to the next op's
scope, the per-phase shares read from it, and idle time by the program's own
host spans."""
import json
import pathlib

import jax
import pytest

from bench import scopes, trace

DATA = pathlib.Path(__file__).resolve().parent / "data"
BODY = "jit(f)/while/body/closed_call/tlb"

# device ops (op_name, start ns, end ns); the window is [100, 1100]
OPS = [
    ("jit(f)/synth/add", 50, 120),  # clipped to the window: 20 ns
    (f"{BODY}/tlb4k/fusion", 150, 250),
    ("jit(f)/while", 300, 800),  # encloses the next three
    (f"{BODY}/tlb2m/add", 320, 420),
    (f"{BODY}/bmc/mul", 500, 600),
    (f"{BODY}/tlb4k/sub", 650, 700),
    (f"{BODY}/bmc/reduce", 900, 1000),
    (None, 1020, 1050),  # an op without an op_name
]
# host spans (name, start ns, end ns)
SPANS = [("bench.window", 100, 1100), ("sim.finalize", 100, 1100), ("serve.init", 0, 50),
         ("serve.step", 110, 260), ("serve.readback", 780, 1100)]


def _xspace() -> str:
    """As the TPU runtime writes it: each op's op_name sits in the "tf_op"
    stat of its event metadata, as "<op_name>:". Every op here has the same
    HLO text, as ops of two programs may: only the metadata id tells them
    apart."""
    meta = ""
    for i, (op, _, _) in enumerate(OPS, 1):
        stat = f'stats {{ metadata_id: 1 str_value: "{op}:" }}' if op else ""
        meta += f'event_metadata {{ key: {i} value {{ id: {i} name: "%fusion.1 = f32[] fusion()" {stat} }} }}\n'
    events = "".join(f"events {{ metadata_id: {i} offset_ps: {s * 1000} "
                     f"duration_ps: {(e - s) * 1000} }}\n" for i, (_, s, e) in enumerate(OPS, 1))
    host_meta = "".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}\n'
                        for i, (n, _, _) in enumerate(SPANS, 1))
    host_events = "".join(f"events {{ metadata_id: {i} offset_ps: {s * 1000} "
                          f"duration_ps: {(e - s) * 1000} }}\n"
                          for i, (_, s, e) in enumerate(SPANS, 1))
    return f"""
planes {{
  id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
{events} }}
{meta}
  stat_metadata {{ key: 1 value {{ id: 1 name: "tf_op" }} }}
}}
planes {{
  id: 2 name: "/host:CPU"
  lines {{ id: 2 name: "python" timestamp_ns: 0
{host_events} }}
{host_meta}
}}
"""


@pytest.fixture(scope="module")
def raw():
    return jax.profiler.ProfileData.text_proto_to_serialized_xspace(_xspace())


@pytest.fixture(scope="module")
def tpu_like(raw):
    return jax.profiler.ProfileData.from_serialized_xspace(raw)


@pytest.fixture(scope="module")
def reduced(tpu_like, raw):
    return scopes.reduce(tpu_like, raw)


def _ns(d):
    return {p: (round(v["self_s"] * 1e9, 6), round(v["gap_before_s"] * 1e9, 6))
            for p, v in d.items()}


def test_self_time_excludes_nested_ops_and_gaps_go_to_the_next_op(reduced):
    assert reduced["window_s"] == pytest.approx(1000e-9)
    assert reduced["busy_s"] == pytest.approx(750e-9)
    assert _ns(reduced["scopes"]) == {
        "jit(f)": (250.0, 50.0),  # the while less its body; the gap before it
        "jit(f)/synth": (20.0, 0.0),
        f"{BODY}/tlb4k": (150.0, 30.0),
        f"{BODY}/tlb2m": (100.0, 0.0),
        f"{BODY}/bmc": (200.0, 100.0),
    }
    # every op's self time adds up to the busy time (the unnamed op's 30 ns)
    assert sum(v["self_s"] for v in reduced["scopes"].values()) == pytest.approx(720e-9)
    # the while's own time and the unnamed op lie under no declared scope
    assert reduced["declared_busy_share"] == pytest.approx(100.0 * 470 / 750)
    assert [n for n, _ in reduced["undeclared_ops"]] == ["%fusion.1 [jit(f)]", "%fusion.1 []"]


def test_sim_shares_and_the_unscoped_remainder_make_the_window(reduced):
    shares = {n: scopes.window_share(reduced, (n,)) for n in ("tlb4k", "tlb2m", "bmc")}
    assert shares == pytest.approx({"tlb4k": 18.0, "tlb2m": 10.0, "bmc": 30.0})
    assert all(0 <= v <= 100 for v in shares.values())
    named = ("tlb4k", "tlb2m", "bmc")
    other = sum(v["self_s"] + v["gap_before_s"] for p, v in reduced["scopes"].items()
                if not scopes.under(p, named))
    accounted = sum(v["self_s"] + v["gap_before_s"] for v in reduced["scopes"].values())
    # the unnamed op, the gap before it and the trailing gap go to no scope
    unassigned = reduced["window_s"] - accounted
    assert unassigned == pytest.approx(100e-9)
    remainder = 100.0 * (other + unassigned) / reduced["window_s"]
    assert remainder == pytest.approx(42.0)
    assert sum(shares.values()) + remainder == pytest.approx(100.0)


def test_readers_read_the_scopes_and_fall_silent_without_them(reduced):
    # each share reads only its own program's scopes
    assert reduced["shares"] == pytest.approx(
        {"sim.tlb4k_share": 18.0, "sim.tlb2m_share": 10.0, "sim.bmc_share": 30.0})
    decode = {**reduced, "scopes": {
        "jit(step)/while/body/closed_call/read": {"self_s": 300e-9, "gap_before_s": 5e-9},
        "jit(step)/while/body/closed_call/attend": {"self_s": 150e-9, "gap_before_s": 0.0},
        "jit(step)/append": {"self_s": 75e-9, "gap_before_s": 0.0},
        "jit(step)/observe": {"self_s": 15e-9, "gap_before_s": 0.0},
        "jit(step)/promote": {"self_s": 15e-9, "gap_before_s": 0.0},
        "jit(step)": {"self_s": 195e-9, "gap_before_s": 0.0}}}
    assert scopes.shares(decode) == pytest.approx(
        {"decode.attend_share": 60.0, "decode.append_share": 10.0,
         "decode.control_share": 4.0})
    assert scopes.window_share(decode, ("tlb4k",)) is None
    # a program whose ops carry op_names but none of the declared scopes
    bare = {**reduced, "scopes": {"jit(f)/while/body": {"self_s": 1e-7, "gap_before_s": 0.0}}}
    assert scopes.shares(bare) == {}
    assert scopes.busy_share(bare, ("read",)) is None


def test_idle_by_span_labels_gaps_with_the_innermost_program_span(tpu_like, reduced):
    idle = scopes.idle_by_span(tpu_like, "tpu")
    assert {k: round(v * 1e9, 6) for k, v in idle.items()} == {
        "serve.readback": 170.0, "sim.finalize": 50.0, "serve.step": 30.0}
    assert reduced["idle_by_span"] == idle


def test_device_events_join_metadata_by_id_on_the_profile_data_clock():
    """Times in whole ns as ProfileData gives them (ps cut down), so the ops
    add up to bench.trace's busy time; each event takes its own metadata."""
    text = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 7
    events { metadata_id: 2 offset_ps: 1500 duration_ps: 2999 }
    events { metadata_id: 1 offset_ps: 4999 duration_ps: 1001 } }
  event_metadata { key: 1 value { id: 1 name: "%f.1 = f32[] f()"
                                  stats { metadata_id: 1 str_value: "jit(a)/x/add:" } } }
  event_metadata { key: 2 value { id: 2 name: "%f.1 = f32[] f()"
                                  stats { metadata_id: 1 str_value: "jit(b)/y/mul:" } } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
}
"""
    raw = jax.profiler.ProfileData.text_proto_to_serialized_xspace(text)
    got = scopes.device_events(raw)["/device:TPU:0"]
    assert got == [("jit(b)/y", "%f.1", 8, 10), ("jit(a)/x", "%f.1", 11, 12)]
    ref = trace.device_ops(jax.profiler.ProfileData.from_serialized_xspace(raw), "tpu")
    assert [(s, e) for _, _, s, e in got] == [(s, e) for _, s, e in ref["/device:TPU:0"]]


# trace.reduce of data/cpu_window.xplane.pb before the reduction had scopes
CPU_BEFORE = {
    "busy_s": 0.007237432,
    "window_s": 0.065730367,
    "chips": 1,
    "device_ops": [["dot_general.2", 0.0037965620000000003], ["dot_general.3", 0.002602782],
                   ["wrapped_tanh", 0.0008380880000000001]],
    "idle_gaps": [["$time sleep", 0.056677995], ["bench.unit", 0.0005642160000000001],
                  ["bench.unit", 0.000434036], ["PjRtCpuExecutable::ExecuteHelper", 0.000342086],
                  ["bench.unit", 0.00024497200000000004], ["bench.unit", 0.000123103],
                  ["bench.unit", 0.00010104800000000001], ["bench.unit", 2.441e-06],
                  ["bench.unit", 2.0900000000000003e-06], ["bench.unit", 9.480000000000001e-07]],
    "spans": {"bench.window": 1, "bench.unit": 3, "bench.idle": 1},
}


def test_the_cpu_trace_reduces_as_before_with_no_scopes(capsys):
    assert trace.reduce(trace.load(DATA), "cpu") == CPU_BEFORE
    assert scopes.main([str(DATA)]) == 0
    red = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert red["scopes"] == {} and red["shares"] == {}
    assert "declared_busy_share" not in red
    assert red["window_s"] == CPU_BEFORE["window_s"] and red["busy_s"] == CPU_BEFORE["busy_s"]
