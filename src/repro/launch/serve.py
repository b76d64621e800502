"""Serving launcher: batched prefill + decode with flat or Rainbow-paged KV.

``PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --reduced \
      --kv paged --tokens 64``

The paged path's controller knobs come from the unified ControlPolicy surface
(engine.policy): pick a registered preset with ``--policy`` and override
individual knobs with ``--interval-steps/--top-n/--hot-slots/--max-promotions``.
``--autotune`` records the decode attention-mass trace of a short pilot run,
searches (interval_steps, threshold_init) engine-in-the-loop against it
(engine.autotune), and serves with the winning policy.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config, get_reduced_config
from repro.engine.policy import available_policies, get_policy
from repro.memory.kvcache import PagedConfig, paged_init
from repro.models import model as M
from repro.serving.rainbow_decode import rainbow_decode_step, record_mass_trace
from repro.serving.steps import greedy_sample
from repro.timing import GEOMETRY_PRESETS, get_geometry
from repro.utils.compile_cache import enable_compile_cache


def resolve_timing(args, error):
    """Validated (timing_model, QueueGeometry | None) from the CLI flags.

    Mirrors EngineSpec.timing_geometry(): "flat" resolves to no geometry and
    REJECTS an explicit --queue-geometry (it would otherwise be silently
    dropped — the same loud-over-lossy rule the --kv flat audit applies to
    the controller knobs); "queueing" resolves the named preset through
    repro.timing.get_geometry, unknown names listed loudly.
    """
    if args.timing_model == "flat":
        if args.queue_geometry is not None:
            error(
                f"--queue-geometry {args.queue_geometry} has no effect under "
                "--timing-model flat; drop it or pass --timing-model queueing"
            )
        return "flat", None
    name = args.queue_geometry or "default"
    try:
        geom = get_geometry(name)
    except KeyError:
        error(
            f"unknown --queue-geometry preset {name!r}; registered: "
            f"{sorted(GEOMETRY_PRESETS)}"
        )
    geom.validate()
    return "queueing", geom


def build_paged_config(nblk: int, block_size: int = 8,
                       policy: str = "serving-default", **knobs) -> PagedConfig:
    """One PagedConfig from (preset, overrides, geometry-aware defaults).

    `knobs` are the CLI's hot_slots / top_n / max_promotions /
    interval_steps; None leaves the preset's value. Precedence: explicit
    knobs > the chosen preset. Geometry-aware fallbacks (hot pool sized to
    the sequence) only apply to the generic "serving-default" preset — a
    named preset's knobs are exactly what its author registered.
    """
    preset = get_policy(policy)
    overrides = {k: v for k, v in knobs.items() if v is not None}
    if policy == "serving-default":
        hot = overrides.get("hot_slots", max(8, nblk // 2))
        overrides.setdefault("hot_slots", hot)
        overrides.setdefault("top_n", min(8, nblk))
        overrides.setdefault("max_promotions", min(16, hot))
    return PagedConfig(
        block_size=block_size,
        blocks_per_seq=nblk,
        policy=preset.replace(**overrides) if overrides else preset,
    )


@dataclasses.dataclass(frozen=True)
class Generation:
    """One batched greedy decode."""

    tokens: jax.Array  # int32[B, new]: the greedy continuation
    logits: jax.Array | None  # f32[B, new, V]: the logits each new token came from
    promoted: int | None  # hot KV blocks promoted (paged cache only)
    seconds: float  # wall time, compilation included
    # routed slots (token, expert) that landed on the experts held here,
    # summed over the call's steps and MoE layers (paged MoE models only)
    local_expert_slots: int | None = None


# The paged decode step, jitted once for every call: a later call with the
# same model, cache config and shapes runs the compiled program it already
# has, and the cache is donated, so each step updates the pools in place
# instead of copying them whole.
_paged_step = jax.jit(rainbow_decode_step, static_argnums=(0, 1),
                      static_argnames=("collect_slots",), donate_argnums=(4,))


def generate(cfg, params, prompt: jax.Array, new_tokens: int,
             pcfg: PagedConfig | None = None, keep_logits: bool = True) -> Generation:
    """Greedy decode after `prompt` over the flat KV cache, or over the
    Rainbow-paged cache when `pcfg` is given. With keep_logits=False only
    the tokens are kept (`logits` is None): the stacked float32 logits of
    a large batch and vocabulary would not fit the device.

    The host work sits in profiler spans on the device trace's clock:
    "serve.init" (cache and step set-up), one "serve.step" step annotation
    per prompt and decode step, and "serve.readback" (the final wait and the
    promoted-block count)."""
    enable_compile_cache()
    b, plen = prompt.shape
    t0 = time.perf_counter()
    # a paged MoE step also returns its routed slots on the held experts
    counted = pcfg is not None and cfg.family == "moe"
    slots = []
    with jax.profiler.TraceAnnotation("serve.init"):
        if pcfg is None:
            cache = M.init_cache(cfg, b, plen + new_tokens, tp=1)
            step = jax.jit(lambda p, t, c: M.decode_step(cfg, p, t, c))
        else:
            cache = paged_init(cfg, pcfg, b, 1, cfg.num_layers)

            def step(p, t, k):
                out = _paged_step(cfg, pcfg, p, t, k, collect_slots=counted)
                slots.extend(out[2:])
                return out[:2]
    if pcfg is None:
        with jax.profiler.StepTraceAnnotation("serve.step", step_num=0):
            logits, cache = M.prefill(cfg, params, {"tokens": prompt}, cache, tp=1)
            logits = logits[:, -1:]
        first = 1
    else:
        # paged path consumes the prompt token-by-token (prefill-by-decode)
        for i in range(plen):
            with jax.profiler.StepTraceAnnotation("serve.step", step_num=i):
                logits, cache = step(params, prompt[:, i:i + 1], cache)
        first = plen
    tokens, seen = [], []
    for i in range(new_tokens):
        with jax.profiler.StepTraceAnnotation("serve.step", step_num=first + i):
            if i:
                logits, cache = step(params, tokens[-1], cache)
            if keep_logits:
                seen.append(logits[:, -1])
            tokens.append(greedy_sample(logits, cfg.vocab_size))
    with jax.profiler.TraceAnnotation("serve.readback"):
        if keep_logits:
            out = jax.block_until_ready(
                (jnp.concatenate(tokens, axis=1), jnp.stack(seen, axis=1)))
        else:
            out = (jax.block_until_ready(jnp.concatenate(tokens, axis=1)), None)
        promoted = None if pcfg is None else int((cache.remap.remap >= 0).sum())
        local = int(jnp.stack(slots).sum()) if counted else None
    return Generation(*out, promoted, time.perf_counter() - t0, local)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--kv", choices=["flat", "paged"], default="paged")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--block-size", type=int, default=8)
    # -- unified ControlPolicy knobs (paged path) --
    ap.add_argument("--policy", default="serving-default",
                    help=f"registered preset, one of {available_policies()}")
    ap.add_argument("--interval-steps", type=int, default=None,
                    help="decode steps per monitoring interval")
    ap.add_argument("--top-n", type=int, default=None,
                    help="stage-2 monitored superblocks")
    ap.add_argument("--hot-slots", type=int, default=None,
                    help="hot-pool capacity in KV blocks")
    ap.add_argument("--max-promotions", type=int, default=None,
                    help="promotion-plan size per interval")
    ap.add_argument("--autotune", action="store_true",
                    help="tune (interval_steps, threshold_init) against a "
                         "recorded pilot decode trace before serving")
    # -- timing model (paged path) --
    ap.add_argument("--timing-model", choices=["flat", "queueing"],
                    default="flat",
                    help="cost model for reporting/tuning: flat event counts "
                         "or the per-channel/bank queueing model")
    ap.add_argument("--queue-geometry", default=None,
                    help="registered QueueGeometry preset, one of "
                         f"{sorted(GEOMETRY_PRESETS)} (queueing model only)")
    args = ap.parse_args()
    timing_model, queue_geom = resolve_timing(args, ap.error)

    cfg = get_reduced_config(args.arch) if args.reduced else get_config(args.arch)
    if args.prompt_len < 1 or args.tokens < 1:
        ap.error("--prompt-len and --tokens must be >= 1")
    if args.kv == "paged" and cfg.family not in ("dense", "vlm") and not cfg.mla:
        ap.error(
            f"--kv paged targets dense-family and latent-attention archs; --arch "
            f"{args.arch} is family {cfg.family!r} (use --kv flat)"
        )
    if args.kv == "flat" and cfg.mla:
        ap.error(f"--arch {args.arch} uses latent attention, which decodes over "
                 "the paged cache only; use --kv paged")
    if args.kv == "flat":
        ignored = [
            flag for flag, v in [
                ("--autotune", args.autotune or None),
                ("--interval-steps", args.interval_steps),
                ("--top-n", args.top_n),
                ("--hot-slots", args.hot_slots),
                ("--max-promotions", args.max_promotions),
                ("--queue-geometry", args.queue_geometry),
                ("--timing-model",
                 None if timing_model == "flat" else timing_model),
            ] if v is not None
        ]
        if args.policy != "serving-default":
            ignored.append("--policy")
        if ignored:
            ap.error(
                f"{', '.join(ignored)} only appl{'y' if len(ignored) > 1 else 'ies'} "
                "to the Rainbow-paged cache; drop the flag(s) or use --kv paged"
            )
    key = jax.random.PRNGKey(0)
    params = M.init_params(cfg, key, tp=1)
    b = args.batch
    total = args.prompt_len + args.tokens
    prompt = jax.random.randint(key, (b, args.prompt_len), 0, cfg.vocab_size)

    pcfg = None
    if args.kv == "paged":
        nblk = (total + args.block_size - 1) // args.block_size
        try:
            pcfg = build_paged_config(
                nblk, args.block_size, args.policy,
                hot_slots=args.hot_slots, top_n=args.top_n,
                max_promotions=args.max_promotions,
                interval_steps=args.interval_steps,
            )
        except (ValueError, KeyError) as e:
            # impossible geometry / unknown preset -> clean CLI error
            ap.error(str(e.args[0]) if e.args else str(e))

        if timing_model == "queueing":
            print(f"timing model: queueing, geometry {queue_geom}")

        if args.autotune:
            from repro.engine.autotune import TunePlan, autotune

            pilot = args.prompt_len + min(args.tokens, 16)
            trace, _ = record_mass_trace(cfg, pcfg, params, prompt, steps=pilot)
            plan = TunePlan.grid(
                pcfg.policy,
                interval_steps=(2, 4, 8, 16),
                threshold_init=(0.0, 64.0),
            )
            res = autotune(plan, trace)
            print(f"autotune ({pilot}-step pilot trace): {res.summary()}")
            tuned = res.tuned_policy()
            # every knob outside the tuned axes (including the async-migration
            # family: async_window / abort_on_write / shadow_residency) must
            # ride through the tuner untouched — a tuned policy that silently
            # reset them would serve a different policy than requested
            tuned_axes = {name for name, _ in plan.space}
            drifted = {
                name
                for name in type(tuned).__dataclass_fields__
                if name not in tuned_axes
                and getattr(tuned, name) != getattr(pcfg.policy, name)
            }
            assert not drifted, (
                f"autotune dropped untuned ControlPolicy knobs: {sorted(drifted)}"
            )
            pcfg = PagedConfig(
                block_size=pcfg.block_size,
                blocks_per_seq=pcfg.blocks_per_seq,
                policy=tuned,
            )

    gen = generate(cfg, params, prompt, args.tokens, pcfg)
    if gen.promoted is not None:
        print(f"promoted hot blocks: {gen.promoted}")
    dt = gen.seconds
    print(f"decoded {args.tokens} tokens x {b} seqs in {dt:.2f}s "
          f"({1000 * dt / args.tokens:.1f} ms/step incl. compile)")
    print("first sequence:", gen.tokens[0].tolist()[:16], "...")


if __name__ == "__main__":
    main()
