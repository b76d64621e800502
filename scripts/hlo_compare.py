"""Compare the optimized HLO of two checkouts, op metadata left out.

    python3 scripts/hlo_compare.py <checkout A> <checkout B>

Compiles ahead of time, for a described TPU v5e chip (no chip needed), the
programs that the benchmark's cells run, at the cells' sizes:

  sim-rainbow, sim-flat-static   the fused simulation program
                                 (`simloop._engine_run_fused_donated`) of the
                                 `gups` configuration, 3 intervals
  decode-step                    `rainbow_decode_step` of `qwen3-0.6b` at the
                                 `decode-b8-p64-o192` traffic's cache size,
                                 as the CPU selects it (the jnp read)
  decode-step-kernel             the same step with the rainbow_attention
                                 kernel reading the pools, as on a TPU

Each checkout is compiled in a process of its own (both hold the same module
names). The HLO text is stripped of `metadata={...}` (op_name, source file
and line) and of the stack-frame tables it points into, and each Mosaic
kernel's serialized body is replaced by the SHA-256 of its MLIR printed
without source locations; what is left is what the device runs. Prints one line per program: "identical",
"identical up to instruction names" (the same text once every `%name` is
renumbered in order of appearance), or "DIFFERENT" with the first lines of
the diff. Exits 1 if any program differs. Takes a few minutes on a CPU.
"""
from __future__ import annotations

import difflib
import json
import os
import pathlib
import re
import subprocess
import sys
import tempfile

PROGRAMS = ("sim-rainbow", "sim-flat-static", "decode-step", "decode-step-kernel")


def strip_metadata(text: str) -> str:
    """The HLO text less every `, metadata={...}` and the stack-frame tables."""
    out, i = [], 0
    while (j := text.find(", metadata={", i)) >= 0:
        out.append(text[i:j])
        k, depth, quoted = j + len(", metadata={"), 1, False
        while depth:
            c = text[k]
            if c == '"' and text[k - 1] != "\\":
                quoted = not quoted
            elif not quoted and c in "{}":
                depth += 1 if c == "{" else -1
            k += 1
        i = k
    out.append(text[i:])
    lines, skip = [], False
    for line in "".join(out).split("\n"):
        if line in ("FileNames", "FunctionNames", "FileLocations", "StackFrames"):
            skip = True
        elif skip and line == "":
            skip = False
        elif not skip:
            lines.append(line)
    return "\n".join(lines)


def kernel_bodies_without_locations(text: str) -> str:
    """`text` with each Mosaic kernel's base64 MLIR bytecode ("body") replaced
    by the SHA-256 of the module printed without debug locations: a kernel
    whose source lines moved is the same kernel."""
    import base64
    import hashlib

    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    def digest(m):
        with ir.Context() as ctx, ir.Location.unknown():
            tpu.register_dialect(ctx)
            ctx.allow_unregistered_dialects = True
            module = ir.Module.parse(base64.b64decode(m.group(1)))
            asm = module.operation.get_asm(enable_debug_info=False)
        return '"body":"sha256:' + hashlib.sha256(asm.encode()).hexdigest() + '"'

    return re.sub(r'"body":"([A-Za-z0-9+/=]+)"', digest, text)


def renumbered(text: str) -> str:
    """`text` with every `%name` replaced by its order of first appearance."""
    ids: dict[str, str] = {}
    return re.sub(r"%[A-Za-z_][\w.\-]*", lambda m: ids.setdefault(m.group(0), f"%v{len(ids)}"),
                  text)


def dump(tree: pathlib.Path, out: pathlib.Path) -> None:
    """Write <program>.hlo (metadata stripped) for each of `PROGRAMS`."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path[:0] = [str(tree), str(tree / "src")]
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    from bench import harness
    from repro.engine import simloop
    from repro.launch import serve
    from repro.memory.kvcache import paged_init
    from repro.serving.rainbow_decode import rainbow_decode_step
    from repro.sim import trace as trace_mod

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    dev = SingleDeviceSharding(topo.devices[0])

    def sds(tree_):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=dev), tree_)

    def record(name, lowered):
        text = strip_metadata(lowered.compile().as_text())
        (out / f"{name}.hlo").write_text(kernel_bodies_without_locations(text))

    bench = harness.Bench(tree)
    cfg = bench.config("gups")
    mc = bench.driver("sim").machine_config(cfg)
    meta = trace_mod.probe_meta(cfg["program_scenario"], cfg["accesses_per_interval"])
    for policy in ("rainbow", "flat-static"):
        spec = simloop.EngineSpec(
            policy=policy, mc=mc, num_superpages=meta["num_superpages"],
            footprint_pages=meta["footprint_pages"],
            source=simloop.TraceSource(scenario=cfg["program_scenario"],
                                       accesses=cfg["accesses_per_interval"]))
        state = jax.eval_shape(lambda: simloop.engine_init(spec))
        seed = jax.ShapeDtypeStruct((), jnp.int32, sharding=dev)
        record(f"sim-{policy}", simloop._engine_run_fused_donated.lower(spec, sds(state), seed, 3))

    decode = bench.driver("decode")
    qcfg = bench.config("qwen3-0.6b")
    mix = bench.traffic("decode-b8-p64-o192")
    mcfg = decode.model_config(qcfg)
    pcfg = serve.build_paged_config(int(mix["blocks_per_seq"]), int(mix["block_size"]),
                                    mix["policy"])
    weights = jax.eval_shape(
        lambda: bench.reference(qcfg["reference"]).make_weights(qcfg, 1, mcfg.padded_vocab))
    params = decode.program_params(weights)
    b = int(mix["batch"])
    kv = jax.eval_shape(lambda: paged_init(mcfg, pcfg, b, 1, mcfg.num_layers))
    tok = jax.ShapeDtypeStruct((b, 1), jnp.int32, sharding=dev)
    step = jax.jit(lambda p, t, k: rainbow_decode_step(mcfg, pcfg, p, t, k))
    record("decode-step", step.lower(sds(params), tok, sds(kv)))
    from repro.kernels.rainbow_attention import ops as ra_ops

    ra_ops.backend = lambda *a, **k: "pallas"  # what it picks on a TPU
    step = jax.jit(lambda p, t, k: rainbow_decode_step(mcfg, pcfg, p, t, k))
    record("decode-step-kernel", step.lower(sds(params), tok, sds(kv)))


def main(argv: list[str]) -> int:
    if len(argv) == 3 and argv[0] == "--dump":
        dump(pathlib.Path(argv[1]).resolve(), pathlib.Path(argv[2]))
        return 0
    if len(argv) != 2:
        print(__doc__, file=sys.stderr)
        return 2
    trees = [pathlib.Path(a).resolve() for a in argv]
    with tempfile.TemporaryDirectory() as tmp:
        outs = [pathlib.Path(tmp) / side for side in "AB"]
        for tree, out in zip(trees, outs):
            out.mkdir()
            subprocess.run([sys.executable, __file__, "--dump", str(tree), str(out)], check=True)
        verdicts = {}
        for name in PROGRAMS:
            a, b = ((out / f"{name}.hlo").read_text() for out in outs)
            if a == b:
                verdicts[name] = "identical"
            elif renumbered(a) == renumbered(b):
                verdicts[name] = "identical up to instruction names"
            else:
                verdicts[name] = "DIFFERENT"
                diff = difflib.unified_diff(a.split("\n"), b.split("\n"), lineterm="", n=1)
                print("\n".join(line[:200] for line in list(diff)[:30]))
    print(json.dumps(verdicts))
    return 1 if "DIFFERENT" in verdicts.values() else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
