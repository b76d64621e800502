"""The whole decode's share, in %, of the chip's peak bf16 FLOP/s over the
traced window: the operations of every step of the window's calls
(bench/flops.py, prompt steps included) over window time x peak."""
from bench.flops import decode_call


def read(ctx):
    c, pk = ctx["counters"], ctx["peaks"]
    window = ctx["trace"]["window_s"]
    if not c.get("calls") or window <= 0 or pk is None:
        return None
    ops, _ = decode_call(ctx["config"], c["batch"], c["steps_per_call"])
    return 100.0 * ops * c["calls"] / (window * pk["bf16_flops"] * ctx["trace"]["chips"])
