"""The latent attention kernel's share, in %, of its roofline: the least
bytes it moves over the window's steps (every live latent row of every layer
read once, bench/flops_mla_moe.py) over HBM bandwidth, against its own
device seconds, the trace op list's entries for its custom call
("latent_attention"). None where the kernel is not among the ops the trace
reduction keeps (it keeps the ten longest)."""
from bench.flops_mla_moe import latent_read


def read(ctx):
    c, pk = ctx["counters"], ctx["peaks"]
    seconds = sum(t for name, t in ctx["trace"]["device_ops"] if "latent_attention" in name)
    if not c.get("calls") or seconds <= 0 or pk is None:
        return None
    nbytes = sum(latent_read(ctx["config"], c["batch"], length)
                 for length in range(c["steps_per_call"]))
    return 100.0 * nbytes * c["calls"] / pk["hbm_bw"] / seconds
