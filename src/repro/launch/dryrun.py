import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
).strip()

# ^ MUST precede every other import (jax locks device count on first init).

"""Multi-pod dry-run driver (deliverable (e)).

For each (arch x shape x mesh) cell: build the step function, jit with explicit
shardings, .lower().compile(), print memory_analysis + cost_analysis, parse the
optimized HLO for collective operand bytes, and write a JSON artifact consumed by
benchmarks/roofline.py and EXPERIMENTS.md.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-4b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""
import argparse
import json
import time
import traceback
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import ARCH_IDS, applicable_shapes, get_config, get_shape
from repro.launch import hlo_analysis, inputs as IN
from repro.launch.mesh import make_production_mesh, mesh_dp_size, mesh_tp_size
from repro.launch.sharding import filter_tree, make_constrainer, sharding_tree
from repro.models import model as M
from repro.serving import steps as serve_steps
from repro.train.step import (
    TrainStepConfig,
    batch_specs,
    build_train_step,
    init_train_state,
    train_state_specs,
)


#: The chip the production mesh stands for; its peaks give the roofline terms.
TARGET_DEVICE_KIND = "TPU v5 lite"


def _cost_get(cost: dict, key: str) -> float:
    if not cost:
        return 0.0
    return float(cost.get(key, 0.0))


def run_cell(
    arch: str,
    shape_id: str,
    multi_pod: bool,
    out_dir: str,
    attn_impl: str = "dense",
    kv_impl: str = "flat",
    remat: str = "full",
    quiet: bool = False,
    tag: str = "",
    resid: str = "tp",
) -> dict:
    cfg = get_config(arch)
    shape = get_shape(shape_id)
    mesh = make_production_mesh(multi_pod=multi_pod)
    tp = mesh_tp_size(mesh)
    dp = mesh_dp_size(mesh)
    chips = mesh.devices.size
    replicated = IN.batch_is_replicated(shape, dp)
    sc = make_constrainer(mesh, strip_batch=replicated)
    seq_axis = IN.seq_axis_for(cfg, shape, dp)

    meta = {
        "arch": arch,
        "shape": shape_id,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": int(chips),
        "device_kind": TARGET_DEVICE_KIND,
        "kind": shape.kind,
        "attn_impl": attn_impl,
        "kv_impl": kv_impl,
        "remat": remat,
        "batch_replicated": replicated,
        "cache_seq_axis": seq_axis,
        "tag": tag,
    }

    t0 = time.time()

    def build_lowered():
        if shape.kind == "train":
            tcfg = TrainStepConfig(tp=tp, remat=remat, attn_impl=attn_impl)
            state_sds = jax.eval_shape(
                lambda: init_train_state(cfg, jax.random.PRNGKey(0), tcfg)
            )
            state_sh = sharding_tree(train_state_specs(cfg, tcfg, dp_size=dp), mesh)
            batch_sds = IN.train_inputs(cfg, shape)
            batch_sh = sharding_tree(
                {k: v for k, v in batch_specs(cfg, replicated).items() if k in batch_sds},
                mesh,
            )
            step = build_train_step(cfg, tcfg, sc=sc)
            lowered = jax.jit(
                step,
                in_shardings=(state_sh, batch_sh),
                out_shardings=(state_sh, None),
                donate_argnums=(0,),
            ).lower(state_sds, batch_sds)
            fn_args = (step, (state_sds, batch_sds))
        elif shape.kind == "prefill":
            params_sds = jax.eval_shape(
                partial(M.init_params, cfg, jax.random.PRNGKey(0), tp)
            )
            params_sh = sharding_tree(M.param_specs(cfg, tp), mesh)
            batch_sds = IN.prefill_inputs(cfg, shape)
            batch_sh = sharding_tree(
                {
                    k: v
                    for k, v in serve_steps.prefill_batch_specs(cfg, replicated).items()
                    if k in batch_sds
                },
                mesh,
            )
            max_len = (
                shape.seq_len // cfg.encoder_seq_divisor
                if cfg.is_encoder_decoder
                else shape.seq_len
            )
            step = serve_steps.build_prefill_step(
                cfg, tp, max_len, sc=sc, attn_impl=attn_impl
            )
            lowered = jax.jit(step, in_shardings=(params_sh, batch_sh)).lower(
                params_sds, batch_sds
            )
            fn_args = (step, (params_sds, batch_sds))
        elif shape.kind == "decode" and kv_impl.startswith("paged"):
            # Rainbow paged decode (the paper's technique on the serving path)
            from jax.sharding import PartitionSpec as PS

            from repro.memory.kvcache import (
                PagedConfig, paged_cache_specs, paged_init, paged_scales_init,
            )
            from repro.serving.rainbow_decode import rainbow_decode_step

            assert cfg.family in ("dense", "vlm"), "paged decode: dense-family"
            b = shape.global_batch
            block = 16
            quant = kv_impl.endswith("-q8")
            pcfg = PagedConfig(
                block_size=block,
                blocks_per_seq=shape.seq_len // block,
                hot_slots=4096,
                top_n=128,
                max_promotions=256,
                interval_steps=8,
                quantize=quant,
            )
            params_sds = jax.eval_shape(
                partial(M.init_params, cfg, jax.random.PRNGKey(0), tp)
            )
            params_sh = sharding_tree(M.param_specs(cfg, tp), mesh)
            kv_sds = jax.eval_shape(
                lambda: paged_init(cfg, pcfg, b, tp, cfg.num_layers)
            )
            kv_sh = sharding_tree(paged_cache_specs(), mesh)
            tok_sh = sharding_tree(
                serve_steps.decode_batch_specs(replicated), mesh
            )["tokens"]
            mode = "sparse" if "sparse" in kv_impl else "full"
            step = partial(
                rainbow_decode_step, cfg, pcfg, tp=tp, sc=sc, mode=mode
            )
            tok_sds2 = jax.ShapeDtypeStruct((b, 1), jnp.int32)
            if quant:
                sc_sds = jax.eval_shape(
                    lambda: paged_scales_init(pcfg, b, cfg.kv_store(tp), cfg.num_layers)
                )
                cap_sc = PS(None, "data", None, "model")
                hot_sc = PS(None, None, None, "model")
                sc_sh = sharding_tree(
                    {"cap_k": cap_sc, "cap_v": cap_sc,
                     "hot_k": hot_sc, "hot_v": hot_sc},
                    mesh,
                )
                fn = lambda p, t, k, s: step(p, t, k, scales=s)
                lowered = jax.jit(
                    fn, in_shardings=(params_sh, tok_sh, kv_sh, sc_sh),
                    donate_argnums=(2, 3),
                ).lower(params_sds, tok_sds2, kv_sds, sc_sds)
                fn_args = (fn, (params_sds, tok_sds2, kv_sds, sc_sds))
            else:
                fn = lambda p, t, k: step(p, t, k)
                lowered = jax.jit(
                    fn, in_shardings=(params_sh, tok_sh, kv_sh), donate_argnums=(2,)
                ).lower(params_sds, tok_sds2, kv_sds)
                fn_args = (fn, (params_sds, tok_sds2, kv_sds))
        else:  # decode (flat cache)
            params_sds = jax.eval_shape(
                partial(M.init_params, cfg, jax.random.PRNGKey(0), tp)
            )
            params_sh = sharding_tree(M.param_specs(cfg, tp), mesh)
            tok_sds, cache_sds, _ = IN.decode_inputs(cfg, shape, tp)
            cache_specs = M.cache_specs(cfg, seq_axis=seq_axis)
            if replicated:
                # batch=1 cells: drop batch-dim sharding (cache batch replicates)
                def _strip_batch(spec: P) -> P:
                    return P(*(None if e == ("pod", "data") else e for e in spec))

                cache_specs = jax.tree.map(
                    _strip_batch, cache_specs, is_leaf=lambda x: isinstance(x, P)
                )
            cache_sh = sharding_tree(cache_specs, mesh)
            step = serve_steps.build_decode_step(cfg, tp, sc=sc)
            tok_sh = sharding_tree(
                serve_steps.decode_batch_specs(replicated), mesh
            )["tokens"]
            lowered = jax.jit(
                step, in_shardings=(params_sh, cache_sh, tok_sh), donate_argnums=(1,)
            ).lower(params_sds, cache_sds, tok_sds["tokens"])
            fn_args = (step, (params_sds, cache_sds, tok_sds["tokens"]))
        return lowered, fn_args

    from repro.models.unroll_flag import set_scan_unroll

    M.set_resid_seq_parallel(resid == "seq")
    meta["resid"] = resid
    # Production lowering (rolled scans): memory analysis + compile proof.
    with mesh:
        set_scan_unroll(False)
        lowered, fn_args = build_lowered()
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
        # Cost lowering (unrolled scans): true flops/bytes/collective counts.
        # (HloCostAnalysis counts while bodies once — see models/unroll_flag.py.)
        # Multi-pod cells skip it: the roofline table is single-pod only.
        t0 = time.time()
        if multi_pod:
            cost_compiled = compiled
            meta["cost_from_rolled_hlo"] = True
        else:
            set_scan_unroll(True)
            try:
                cost_compiled = build_lowered()[0].compile()
            finally:
                set_scan_unroll(False)
        t_cost = time.time() - t0

    # ---- analyses ----
    try:
        mem = compiled.memory_analysis()
        mem_stats = {
            "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
            "generated_code_bytes": int(getattr(mem, "generated_code_size_in_bytes", 0)),
            "alias_bytes": int(getattr(mem, "alias_size_in_bytes", 0)),
        }
        mem_stats["peak_bytes_per_device"] = (
            mem_stats["argument_bytes"]
            + mem_stats["output_bytes"]
            + mem_stats["temp_bytes"]
            - mem_stats["alias_bytes"]
        )
    except Exception as e:  # pragma: no cover
        mem_stats = {"error": repr(e)}

    try:
        cost = cost_compiled.cost_analysis()
        if isinstance(cost, list):
            cost = cost[0]
    except Exception as e:  # pragma: no cover
        cost = {"error": repr(e)}

    hlo = cost_compiled.as_text()
    coll = hlo_analysis.collective_bytes(hlo)

    try:
        from repro.launch.jaxpr_flops import count_flops

        fn, fargs = fn_args
        jaxpr_total_flops = count_flops(fn, *fargs)
    except Exception as e:  # pragma: no cover
        jaxpr_total_flops = -1.0

    flops_dev = _cost_get(cost, "flops")
    bytes_dev = _cost_get(cost, "bytes accessed")
    terms = hlo_analysis.roofline_terms(
        flops_dev, bytes_dev, coll.total_bytes, device_kind=TARGET_DEVICE_KIND
    )
    mflops = hlo_analysis.model_flops(cfg, shape, shape.kind)
    useful_ratio = mflops / (flops_dev * chips) if flops_dev else 0.0

    result = {
        **meta,
        "ok": True,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "cost_compile_s": round(t_cost, 2),
        "memory": mem_stats,
        "cost_analysis": {
            k: float(v) for k, v in cost.items() if isinstance(v, (int, float))
        },
        "collectives": {
            "bytes_by_op": coll.bytes_by_op,
            "count_by_op": coll.count_by_op,
            "total_bytes_per_device": coll.total_bytes,
        },
        "roofline": terms,
        "model_flops": mflops,
        "jaxpr_flops_global": jaxpr_total_flops,
        "useful_flops_ratio": useful_ratio,
        "hlo_bytes": len(hlo),
    }

    if not quiet:
        print(f"== {arch} x {shape_id} x {meta['mesh']} ({shape.kind}) ==")
        print(f"  memory_analysis: {mem_stats}")
        print(
            f"  cost_analysis: flops/device={flops_dev:.3e} bytes/device={bytes_dev:.3e}"
        )
        print(
            f"  collectives: {coll.bytes_by_op} total={coll.total_bytes:.3e} B/device"
        )
        print(
            f"  roofline: compute={terms['compute_s']:.4f}s memory={terms['memory_s']:.4f}s"
            f" collective={terms['collective_s']:.4f}s dominant={terms['dominant']}"
            f" useful_flops_ratio={useful_ratio:.3f}"
        )
        print(f"  lower={t_lower:.1f}s compile={t_compile:.1f}s")

    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        suffix = f"__{tag}" if tag else ""
        fname = f"{arch}__{shape_id}__{meta['mesh']}{suffix}.json"
        with open(os.path.join(out_dir, fname), "w") as f:
            json.dump(result, f, indent=2, default=str)
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--attn", default="dense", choices=["dense", "chunked"])
    ap.add_argument("--kv", default="flat", choices=["flat", "paged", "paged-sparse", "paged-q8", "paged-sparse-q8"])
    ap.add_argument("--remat", default="full", choices=["none", "full", "dots"])
    ap.add_argument("--resid", default="tp", choices=["tp", "seq"])
    ap.add_argument("--tag", default="")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    cells: list[tuple[str, str]] = []
    if args.all:
        for arch in ARCH_IDS:
            for shape_id in applicable_shapes(arch):
                cells.append((arch, shape_id))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all required"
        cells.append((args.arch, args.shape))

    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    failures = []
    for arch, shape_id in cells:
        for mp in meshes:
            mesh_name = "2x16x16" if mp else "16x16"
            suffix = f"__{args.tag}" if args.tag else ""
            fpath = os.path.join(
                args.out, f"{arch}__{shape_id}__{mesh_name}{suffix}.json"
            )
            if args.skip_existing and os.path.exists(fpath):
                print(f"skip existing {fpath}")
                continue
            try:
                run_cell(
                    arch, shape_id, mp, args.out,
                    attn_impl=args.attn, kv_impl=args.kv, remat=args.remat,
                    tag=args.tag, resid=args.resid,
                )
            except Exception as e:
                traceback.print_exc()
                failures.append((arch, shape_id, mesh_name, repr(e)))
    if failures:
        print("\nFAILED CELLS:")
        for f in failures:
            print(" ", f)
        raise SystemExit(1)
    print("\nall requested cells passed")


if __name__ == "__main__":
    main()
