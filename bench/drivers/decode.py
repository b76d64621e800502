"""Driver of the paged-decode cells: closed-loop lockstep request batches.

One unit is one `repro.launch.serve.generate` call over the Rainbow-paged
KV cache: a batch of prompts drawn from fold_in(seed, k) for the k-th unit,
greedy decoding of `new_tokens` tokens. Work is generated tokens. The
weights are made from the seed by the reference module in one jitted call
and handed to the program in its parameter layout. The check runs the
plain float32 reference over a sample of the window's sequences (prompt and
served tokens) and reads, for every served token, how far its reference
logit lies below the reference's best at that position.
"""
from __future__ import annotations

import dataclasses

import numpy as np

SEED_MOD = 2**31


def model_config(cfg: dict):
    """The program's ModelConfig for the configuration file, as run."""
    from repro.configs import get_config

    if cfg["rms_norm_eps"] != 1e-6 or cfg["hidden_act"] != "silu" or cfg["attention_bias"]:
        raise ValueError("the program's dense decoder has eps 1e-6, SiLU and no biases")
    return dataclasses.replace(
        get_config(cfg["program_arch"]),
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"], num_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        head_dim=cfg["head_dim"], qk_norm=True, rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=cfg["tie_word_embeddings"], dtype=cfg["torch_dtype"],
        param_dtype=cfg["torch_dtype"],
    )


def program_params(w: dict) -> dict:
    """The reference layout's arrays, nested as the program's parameters."""
    if "head" in w:
        raise ValueError("untied output heads are not laid out here")
    return {
        "embed": {"tok": w["embed"]},
        "segments": {"blocks": {
            "ln1": {"scale": w["ln1"]},
            "attn": {"wq": w["wq"], "wk": w["wk"], "wv": w["wv"], "wo": w["wo"],
                     "q_norm": w["q_norm"], "k_norm": w["k_norm"]},
            "ln2": {"scale": w["ln2"]},
            "mlp": {"wi": w["w_up"], "wg": w["w_gate"], "wo": w["w_down"]},
        }},
        "final_norm": {"scale": w["final_norm"]},
    }


class Driver:
    span = "generate"

    def __init__(self, cfg: dict, mix: dict, seed: int, devices, reference):
        import jax

        from repro.launch import serve

        self.cfg, self.mix, self.ref, self.seed = cfg, mix, reference, seed
        self.mcfg = model_config(cfg)
        self.batch, self.prompt_len = int(mix["batch"]), int(mix["prompt_len"])
        self.new_tokens = int(mix["new_tokens"])
        self.weights = reference.make_weights(cfg, seed, self.mcfg.padded_vocab)
        self.params = program_params(self.weights)
        self.pcfg = serve.build_paged_config(int(mix["blocks_per_seq"]),
                                             int(mix["block_size"]), mix["policy"])
        self._generate = serve.generate
        self._key = jax.random.PRNGKey(seed % SEED_MOD)
        self.done: list[tuple[np.ndarray, np.ndarray]] = []
        self.promoted: list[int] = []
        self._run(self._prompt(-1))  # warm-up: every shape of the window

    def _prompt(self, k: int):
        import jax

        return jax.random.randint(jax.random.fold_in(self._key, k % SEED_MOD),
                                  (self.batch, self.prompt_len), 0, self.cfg["vocab_size"])

    def _run(self, prompt):
        gen = self._generate(self.mcfg, self.params, prompt, self.new_tokens, self.pcfg)
        return np.asarray(gen.tokens), gen.promoted

    def unit(self) -> int:
        prompt = self._prompt(len(self.done))
        tokens, promoted = self._run(prompt)
        self.done.append((np.asarray(prompt), tokens))
        self.promoted.append(promoted)
        return self.batch * self.new_tokens

    def counters(self) -> dict:
        steps = self.prompt_len + self.new_tokens - 1
        return {"calls": len(self.done), "batch": self.batch, "steps_per_call": steps,
                "prompt_len": self.prompt_len, "new_tokens": self.new_tokens,
                "promoted_blocks": sum(self.promoted)}

    def release(self) -> None:
        """The program keeps no cache between calls; its parameter tree goes."""
        self.params = None

    def sample(self):
        """The sequences the check reads, drawn from the seed over the window:
        (prompts, served tokens, the unit each came from)."""
        rows = [(u, b) for u in range(len(self.done)) for b in range(self.batch)]
        rng = np.random.default_rng(self.seed)
        pick = sorted(rng.choice(len(rows), size=min(int(self.mix["check_sequences"]), len(rows)),
                                 replace=False))
        prompt = np.stack([self.done[rows[i][0]][0][rows[i][1]] for i in pick])
        served = np.stack([self.done[rows[i][0]][1][rows[i][1]] for i in pick])
        return prompt, served, np.asarray([rows[i][0] for i in pick])

    def check(self) -> tuple[dict, int]:
        """(widest gap against its limit, units with a sequence over it)."""
        prompt, served, unit = self.sample()
        gaps = self.ref.served_gaps(self.cfg, self.weights, prompt, served)
        limit = self.mix["limits"]["widest_gap"]
        failed = len(set(unit[gaps.max(axis=1) > limit].tolist()))
        return {"widest_gap": {"value": float(gaps.max()), "limit": limit}}, failed


def readings(cfg: dict, mix: dict, seeds, devices, reference, program: bool = True):
    """Per seed (new weights and prompts): the widest gap of the program's
    served tokens and of the tokens the fp8 control puts first."""
    import jax

    drv = Driver(cfg, mix, seeds[0], devices, reference)
    for seed in seeds:
        drv.seed, drv.done = seed, []
        drv.weights = reference.make_weights(cfg, seed, drv.mcfg.padded_vocab)
        drv.params = program_params(drv.weights)
        drv._key = jax.random.PRNGKey(seed % SEED_MOD)
        drv.unit()
        prompt, served, _ = drv.sample()
        gaps, ctrl = reference.served_gaps(cfg, drv.weights, prompt, served, quant=reference.fp8)
        yield {"seed": seed, "tokens": int(gaps.size),
               "program": {"widest_gap": float(gaps.max()),
                           "tokens_off_best": int((gaps > 0).sum())},
               "control": {"widest_gap": float(ctrl.max()),
                           "tokens_off_best": int((ctrl > 0).sum())}}
