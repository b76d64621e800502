"""Driver of the paged-decode cells of latent-attention MoE models (the
DeepSeek-V3 block): closed-loop lockstep request batches.

The loop and the sample are the dense decode driver's
(`bench/drivers/decode.py`): one unit is one `repro.launch.serve.generate`
call over the Rainbow-paged cache and work is generated tokens. Here
`generate` keeps only the tokens (a batch of 128 would stack 16 GB of
logits), and it counts the routed slots that landed on the experts this
chip holds, which the counters expose as `local_expert_slots` (the window's
calls summed).

The check runs the plain float32 reference over the sampled sequences and
compares the share of served tokens that are not the reference's best at
their position (`off_best_share`). The widest gap, the dense cell's number,
does not separate bfloat16 from the fp8 control here: a rounding that flips
a near-tied expert choice moves a whole expert's share of a token's update,
so a bfloat16 program's widest gap over 1,536 tokens reaches the control's
(PERF.md, section 2); how many tokens it moves off the best does not.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench.drivers import decode as base

SEED_MOD = base.SEED_MOD


def model_config(cfg: dict):
    """The program's ModelConfig for the configuration file, as run."""
    from repro.configs import get_config

    if (cfg["model_type"] != "deepseek_v3" or cfg["q_lora_rank"] is not None
            or cfg["hidden_act"] != "silu" or cfg["attention_bias"]
            or cfg["scoring_func"] != "sigmoid" or cfg["topk_method"] != "noaux_tc"
            or cfg["n_group"] != 1 or cfg["topk_group"] != 1 or cfg["moe_layer_freq"] != 1
            or cfg.get("rope_scaling") or cfg["tie_word_embeddings"]):
        raise ValueError("the program's latent-attention MoE decoder is DeepSeek-V3's block "
                         "without a query LoRA, rope scaling or expert groups, SiLU, no "
                         "attention biases, sigmoid noaux_tc routing and an untied head")
    return dataclasses.replace(
        get_config(cfg["program_arch"]),
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"], num_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        head_dim=cfg["v_head_dim"], moe_num_experts=cfg["n_routed_experts"],
        moe_top_k=cfg["num_experts_per_tok"], moe_num_shared=cfg["n_shared_experts"],
        moe_d_ff=cfg["moe_intermediate_size"], moe_first_dense=cfg["first_k_dense_replace"],
        moe_scoring="sigmoid", moe_norm_topk=cfg["norm_topk_prob"],
        moe_routed_scale=float(cfg["routed_scaling_factor"]),
        moe_experts_held=cfg["n_routed_experts_held"], moe_expert_offset=0,
        mla_kv_rank=cfg["kv_lora_rank"], mla_nope_dim=cfg["qk_nope_head_dim"],
        mla_rope_dim=cfg["qk_rope_head_dim"], mla_v_dim=cfg["v_head_dim"],
        rope_theta=float(cfg["rope_theta"]), rope_interleave=True,
        norm_eps=cfg["rms_norm_eps"], tie_embeddings=False,
        dtype=cfg["torch_dtype"], param_dtype=cfg["torch_dtype"],
    )


def program_params(w: dict, cfg: dict) -> dict:
    """The reference layout's arrays, nested as the program's parameters
    (W_kv_b split into W_UK and W_UV; the router in float32)."""
    import jax.numpy as jnp

    nd, nope = cfg["first_k_dense_replace"], cfg["qk_nope_head_dim"]

    def attn(sl):
        return {"wq": w["wq"][sl], "wkv_a": w["wkv_a"][sl], "kv_norm": w["kv_norm"][sl],
                "w_uk": w["wkv_b"][sl, ..., :nope], "w_uv": w["wkv_b"][sl, ..., nope:],
                "wo": w["wo"][sl]}

    dense, moe = slice(0, nd), slice(nd, None)
    return {
        "embed": {"tok": w["embed"], "head": w["head"]},
        "segments": {
            "dense0": {"ln1": {"scale": w["ln1"][dense]}, "attn": attn(dense),
                       "ln2": {"scale": w["ln2"][dense]},
                       "mlp": {"wi": w["w_up"], "wg": w["w_gate"], "wo": w["w_down"]}},
            "blocks": {"ln1": {"scale": w["ln1"][moe]}, "attn": attn(moe),
                       "ln2": {"scale": w["ln2"][moe]},
                       "moe": {"router": w["router"].astype(jnp.float32),
                               "router_bias": w["router_bias"],
                               "wi": w["e_up"], "wg": w["e_gate"], "wo": w["e_down"],
                               "shared": {"wi": w["s_up"], "wg": w["s_gate"],
                                          "wo": w["s_down"]}}},
        },
        "final_norm": {"scale": w["final_norm"]},
    }


class Driver(base.Driver):
    span = "generate"

    def __init__(self, cfg: dict, mix: dict, seed: int, devices, reference):
        import jax

        from repro.launch import serve

        self.cfg, self.mix, self.ref, self.seed = cfg, mix, reference, seed
        self.mcfg = model_config(cfg)
        self.batch, self.prompt_len = int(mix["batch"]), int(mix["prompt_len"])
        self.new_tokens = int(mix["new_tokens"])
        self.weights = reference.make_weights(cfg, seed, self.mcfg.padded_vocab)
        self.params = program_params(self.weights, cfg)
        self.pcfg = serve.build_paged_config(int(mix["blocks_per_seq"]),
                                             int(mix["block_size"]), mix["policy"])
        self._generate = serve.generate
        self._key = jax.random.PRNGKey(seed % SEED_MOD)
        self.done: list[tuple[np.ndarray, np.ndarray]] = []
        self.promoted: list[int] = []
        self.slots: list[int] = []  # routed slots on the held experts, per unit
        self._run(self._prompt(-1))  # warm-up: every shape of the window

    def _run(self, prompt):
        gen = self._generate(self.mcfg, self.params, prompt, self.new_tokens, self.pcfg,
                             keep_logits=False)
        self._slots = gen.local_expert_slots
        return np.asarray(gen.tokens), gen.promoted

    def unit(self) -> int:
        work = super().unit()
        self.slots.append(self._slots)
        return work

    def counters(self) -> dict:
        return {**super().counters(), "local_expert_slots": sum(self.slots)}

    def check(self) -> tuple[dict, int]:
        """(share of served tokens off the reference's best against its
        limit, units whose sampled tokens are over it)."""
        prompt, served, unit = self.sample()
        off = self.ref.served_gaps(self.cfg, self.weights, prompt, served) > 0
        limit = self.mix["limits"]["off_best_share"]
        failed = sum(off[unit == u].mean() > limit for u in set(unit.tolist()))
        return {"off_best_share": {"value": float(off.mean()), "limit": limit}}, int(failed)


def readings(cfg: dict, mix: dict, seeds, devices, reference, program: bool = True):
    """Per seed (new weights and prompts), for the program's served tokens,
    the tokens the fp8 control puts first and those a bfloat16 witness puts
    first (the reference with every matmul operand rounded to bfloat16, the
    configuration's own precision): the share off the reference's best over
    the sampled sequences, its least and largest share in one sequence, and
    the widest gap."""
    import jax

    def read(gaps):
        seq = (gaps > 0).mean(axis=1)
        return {"off_best_share": float((gaps > 0).mean()),
                "sequence_share_min": float(seq.min()), "sequence_share_max": float(seq.max()),
                "widest_gap": float(gaps.max())}

    drv = Driver(cfg, mix, seeds[0], devices, reference)
    for seed in seeds:
        drv.seed, drv.done = seed, []
        drv.weights = reference.make_weights(cfg, seed, drv.mcfg.padded_vocab)
        drv.params = program_params(drv.weights, cfg)
        drv._key = jax.random.PRNGKey(seed % SEED_MOD)
        drv.unit()
        prompt, served, _ = drv.sample()
        gaps, ctrl = reference.served_gaps(cfg, drv.weights, prompt, served, quant=reference.fp8)
        _, witness = reference.served_gaps(cfg, drv.weights, prompt, served, quant=reference.bf16)
        yield {"seed": seed, "tokens": int(gaps.size), "program": read(gaps),
               "control": read(ctrl), "witness": read(witness)}
