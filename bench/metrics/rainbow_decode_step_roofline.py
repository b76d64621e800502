"""Share, in %, of the device's busy time that the decode steps of the
traced window would need at the chip's roofline: for each step the larger of
its operations over peak bf16 FLOP/s and its least bytes over HBM bandwidth
(bench/flops.py), summed, over the busy time of the window."""
from bench.flops import decode_step


def read(ctx):
    c, pk = ctx["counters"], ctx["peaks"]
    busy = ctx["trace"]["busy_s"]
    if not c.get("calls") or busy <= 0 or pk is None:
        return None
    least = 0.0
    for length in range(c["steps_per_call"]):
        ops, nbytes = decode_step(ctx["config"], c["batch"], length)
        least += max(ops / pk["bf16_flops"], nbytes / pk["hbm_bw"])
    return 100.0 * least * c["calls"] / busy
