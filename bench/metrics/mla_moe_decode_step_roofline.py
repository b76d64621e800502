"""Share, in %, of the device's busy time that the decode steps of the
traced window would need at the chip's roofline: for each step the larger of
its operations over peak bf16 FLOP/s and its least bytes over HBM bandwidth
(bench/flops_mla_moe.py), summed, over the busy time of the window. The
routed experts' operations come from the program's count of routed slots on
the held experts (`local_expert_slots`), spread evenly over the steps."""
from bench.flops_mla_moe import decode_step, expert_ops


def read(ctx):
    c, pk = ctx["counters"], ctx["peaks"]
    busy = ctx["trace"]["busy_s"]
    if not c.get("calls") or "local_expert_slots" not in c or busy <= 0 or pk is None:
        return None
    steps = c["steps_per_call"]
    experts = expert_ops(ctx["config"], c["local_expert_slots"] / (c["calls"] * steps))
    least = 0.0
    for length in range(steps):
        ops, nbytes = decode_step(ctx["config"], c["batch"], length)
        least += max((ops + experts) / pk["bf16_flops"], nbytes / pk["hbm_bw"])
    return 100.0 * least * c["calls"] / busy
