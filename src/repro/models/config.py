"""Model configuration covering all 10 assigned architecture families."""
from __future__ import annotations

import dataclasses
import math
from typing import Literal

Family = Literal["dense", "moe", "audio", "hybrid", "vlm", "ssm"]


def _ceil_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: Family
    num_layers: int
    d_model: int
    num_heads: int  # query heads (0 for attn-free)
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 128

    # --- MoE ---
    moe_num_experts: int = 0
    moe_top_k: int = 0
    moe_num_shared: int = 0
    moe_d_ff: int = 0  # per-expert ffn width (fine-grained MoE)
    moe_first_dense: int = 0  # leading dense-FFN layers (deepseek layer 0)
    moe_capacity_factor: float = 1.25
    # routing: "softmax" over the experts, or "sigmoid" scores whose top-k is
    # chosen with a learned correction bias added (DeepSeek-V3 noaux_tc); the
    # top-k weights are renormalized or not, then scaled
    moe_scoring: Literal["softmax", "sigmoid"] = "softmax"
    moe_norm_topk: bool = True
    moe_routed_scale: float = 1.0
    # the experts held here, under expert parallelism: ids
    # moe_expert_offset .. + moe_experts_held - 1 (0 = all of them); the
    # router keeps all moe_num_experts outputs
    moe_experts_held: int = 0
    moe_expert_offset: int = 0

    # --- multi-head latent attention (DeepSeek-V2/V3); 0 = off ---
    mla_kv_rank: int = 0  # latent (compressed KV) width
    mla_nope_dim: int = 0  # per-head query/key width without rotary embedding
    mla_rope_dim: int = 0  # rotary width: per query head, and one key shared by all
    mla_v_dim: int = 0  # per-head value width

    # --- SSM (mamba2 / hymba) ---
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_conv_width: int = 4
    ssm_chunk: int = 256

    # --- attention flavor ---
    qk_norm: bool = False
    rope_theta: float = 1e6
    rope_interleave: bool = False  # rotary pairs are adjacent lanes (DeepSeek-V3)
    sliding_window: int = 0  # 0 = full attention
    global_attn_every: int = 0  # hybrid: every k-th layer uses full attention

    # --- enc-dec (whisper) ---
    is_encoder_decoder: bool = False
    num_encoder_layers: int = 0
    encoder_seq_divisor: int = 2  # enc frames = seq_len // divisor

    # --- multimodal stub frontends ---
    num_vision_tokens: int = 0  # vlm: patch embeddings prepended (stub input)

    # --- norms/activations ---
    norm: Literal["rmsnorm", "layernorm"] = "rmsnorm"
    norm_eps: float = 1e-6
    act: Literal["silu", "gelu"] = "silu"
    gated_mlp: bool = True
    tie_embeddings: bool = False

    # --- numerics ---
    dtype: str = "bfloat16"
    param_dtype: str = "bfloat16"

    # --- padding for TP (computed; see padded_* properties) ---
    vocab_pad_multiple: int = 256

    @property
    def attn_free(self) -> bool:
        return self.num_heads == 0

    @property
    def padded_vocab(self) -> int:
        return _ceil_to(self.vocab_size, self.vocab_pad_multiple)

    @property
    def mla(self) -> bool:
        return self.mla_kv_rank > 0

    @property
    def latent_width(self) -> int:
        """Lanes of one cached MLA row: the latent and the shared rotary key,
        padded to the 128-lane tile."""
        return _ceil_to(self.mla_kv_rank + self.mla_rope_dim, 128)

    @property
    def moe_held(self) -> int:
        return self.moe_experts_held or self.moe_num_experts

    def padded_heads(self, tp: int) -> int:
        if self.attn_free:
            return 0
        return _ceil_to(self.num_heads, tp)

    def kv_store(self, tp: int) -> int:
        """Stored kv-head slots under tp-way sharding (MaxText-style replication).

        kv >= tp: pad to a multiple of tp (no replication). kv < tp: exactly tp
        slots, slot j holding original head (j*kv)//tp (proportional stretch; exact
        GQA grouping whenever tp % kv == 0 -- see DESIGN.md section 5). Guarantees
        padded_heads(tp) % kv_store(tp) == 0 so the q->kv map is a local repeat.
        """
        if self.attn_free:
            return 0
        kv = self.num_kv_heads
        return _ceil_to(kv, tp) if kv >= tp else tp

    @property
    def ssm_d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.ssm_d_inner // self.ssm_head_dim if self.ssm_state else 0

    def param_count(self) -> int:
        """Approximate parameter count (for 6·N·D roofline bookkeeping)."""
        d, l, v = self.d_model, self.num_layers, self.vocab_size
        n = v * d * (1 if self.tie_embeddings else 2)
        hd = self.head_dim
        per_layer = 0
        if self.mla:
            per_layer += self._mla_params()
        elif not self.attn_free:
            h, kv = self.num_heads, self.num_kv_heads
            per_layer += d * h * hd + 2 * d * kv * hd + h * hd * d
        if self.family == "moe":
            e, fe = self.moe_num_experts, self.moe_d_ff
            factor = 3 if self.gated_mlp else 2
            per_layer += d * e  # router
            per_layer += e * factor * d * fe
            per_layer += self.moe_num_shared * factor * d * fe
        elif self.d_ff:
            factor = 3 if self.gated_mlp else 2
            per_layer += factor * d * self.d_ff
        if self.ssm_state:
            di = self.ssm_d_inner
            per_layer += d * (2 * di + 2 * self.ssm_state)  # in_proj (x,z,B,C approx)
            per_layer += di * d  # out_proj
            per_layer += di * self.ssm_conv_width
        n += l * per_layer
        if self.is_encoder_decoder:
            h, kv = self.num_heads, self.num_kv_heads
            enc_per = d * h * hd + 2 * d * kv * hd + h * hd * d
            factor = 3 if self.gated_mlp else 2
            enc_per += factor * d * self.d_ff
            n += self.num_encoder_layers * enc_per
            # decoder cross-attention
            n += l * (d * h * hd + 2 * d * kv * hd + h * hd * d)
        return n

    def _mla_params(self) -> int:
        d, h, r = self.d_model, self.num_heads, self.mla_kv_rank
        qk = self.mla_nope_dim + self.mla_rope_dim
        return (d * h * qk + d * (r + self.mla_rope_dim) + r
                + r * h * (self.mla_nope_dim + self.mla_v_dim) + h * self.mla_v_dim * d)

    def active_param_count(self) -> int:
        """Active params per token (MoE counts only routed top-k + shared)."""
        if self.family != "moe":
            return self.param_count()
        d, l = self.d_model, self.num_layers
        n = self.vocab_size * d * 2
        hd = self.head_dim
        h, kv = self.num_heads, self.num_kv_heads
        per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + d * self.moe_num_experts
        if self.mla:
            per_layer += self._mla_params() - (d * h * hd + 2 * d * kv * hd + h * hd * d)
        factor = 3 if self.gated_mlp else 2
        per_layer += (self.moe_top_k + self.moe_num_shared) * factor * d * self.moe_d_ff
        return n + l * per_layer


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    """One assigned input-shape cell."""

    name: str
    seq_len: int
    global_batch: int
    kind: Literal["train", "prefill", "decode"]


SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}
