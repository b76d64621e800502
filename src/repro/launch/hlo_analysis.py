"""HLO-text analysis: collective operand bytes + roofline terms (§Roofline).

`cost_analysis()` gives per-device HLO FLOPs/bytes; collective bytes are NOT in
cost_analysis, so we parse the optimized HLO: for every all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute instruction we sum the byte sizes
of its operands (resolved through each operand's defining instruction).
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
    "s4": 1, "u4": 1, "token": 0, "opaque": 0,
}

COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all", "collective-permute",
)

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*(\([^)]*\)|[\w\[\],{}\s\/#]+?)\s+([\w\-]+)(?:\.\d+)?\("
)


def shape_bytes(type_str: str) -> int:
    """Bytes of an HLO type string, e.g. 'f32[128,256]{1,0}' or a tuple."""
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dtype, dims = m.group(1), m.group(2)
        if dtype not in DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * DTYPE_BYTES[dtype]
    return total


@dataclass
class CollectiveStats:
    bytes_by_op: dict = field(default_factory=dict)
    count_by_op: dict = field(default_factory=dict)

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_op.values())


def collective_bytes(hlo_text: str) -> CollectiveStats:
    """Sum operand bytes of every collective instruction in the HLO module."""
    # result-shape table: instruction name -> bytes
    result_bytes: dict[str, int] = {}
    instrs: list[tuple[str, str, str]] = []  # (opcode, name, full line)
    for line in hlo_text.splitlines():
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name, type_str, opcode = m.group(1), m.group(2), m.group(3)
        result_bytes[name] = shape_bytes(type_str)
        base_op = opcode.rstrip("0123456789").rstrip(".")
        if any(base_op.startswith(c) for c in COLLECTIVES):
            instrs.append((base_op, name, line))

    stats = CollectiveStats()
    for opcode, name, line in instrs:
        # operands: %name refs inside the call parens
        call = line.split("(", 1)[1]
        call = call.split(")", 1)[0]
        ops = re.findall(r"%?([\w\.\-]+)", call)
        b = 0
        for o in ops:
            if o in result_bytes:
                b += result_bytes[o]
        if b == 0:
            # start-done pairs (e.g. all-reduce-start): charge result size
            b = result_bytes.get(name, 0)
        stats.bytes_by_op[opcode] = stats.bytes_by_op.get(opcode, 0) + b
        stats.count_by_op[opcode] = stats.count_by_op.get(opcode, 0) + 1
    return stats


# ---------------------------------------------------------------------------
# Roofline terms: published per-chip peaks, keyed by jax's device_kind
# ---------------------------------------------------------------------------

#: Google Cloud TPU documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at
#: 819 GB/s, 1,600 Gbit/s of interconnect over 4 links (50 GB/s per link).
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}


def peaks(device_kind: str) -> dict[str, float]:
    """Peak bf16 FLOP/s, HBM B/s and ICI B/s per link of one chip."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None


def roofline_terms(
    flops_per_device: float,
    bytes_per_device: float,
    collective_bytes_per_device: float,
    *,
    device_kind: str,
) -> dict[str, float]:
    pk = peaks(device_kind)
    compute_s = flops_per_device / pk["flops"]
    memory_s = bytes_per_device / pk["hbm_bw"]
    collective_s = collective_bytes_per_device / pk["ici_bw"]
    terms = {"compute_s": compute_s, "memory_s": memory_s, "collective_s": collective_s}
    dom = max(terms, key=terms.get)
    terms["dominant"] = dom
    bound = max(compute_s, memory_s, collective_s)
    terms["roofline_fraction"] = compute_s / bound if bound > 0 else 0.0
    return terms


def decode_bytes_global(cfg, shape) -> float:
    """Analytic per-step HBM traffic for decode cells (global bytes).

    XLA's HloCostAnalysis charges dynamic-update-slice as full-buffer
    read+write; on TPU the update is in-place and tiny, so for decode the raw
    'bytes accessed' is inflated by ~2*L*cache_bytes. This analytic model is
    the corrected memory-term source for decode cells (documented in
    EXPERIMENTS.md §Roofline): params + one full KV/state read + logits.
    """
    n_params = cfg.param_count()
    b, s = shape.global_batch, shape.seq_len
    bytes_total = 2.0 * n_params  # bf16 weights read once
    hd = cfg.head_dim
    kvs = cfg.kv_store(16)
    if cfg.is_encoder_decoder:
        s_eff = s // cfg.encoder_seq_divisor
        # decoder self KV + cross KV
        bytes_total += 2 * cfg.num_layers * b * s_eff * kvs * hd * 2 * 2
    elif not cfg.attn_free:
        window = cfg.sliding_window
        if window and cfg.global_attn_every:
            n_glob = (cfg.num_layers + cfg.global_attn_every - 1) // cfg.global_attn_every
            n_loc = cfg.num_layers - n_glob
            s_loc = min(window, s)
            bytes_total += 2 * b * hd * kvs * 2 * (n_glob * s + n_loc * s_loc)
        else:
            bytes_total += 2 * cfg.num_layers * b * s * kvs * hd * 2
    if cfg.ssm_state:
        h = cfg.ssm_d_inner // cfg.ssm_head_dim
        bytes_total += cfg.num_layers * b * h * cfg.ssm_head_dim * cfg.ssm_state * 4 * 2
    bytes_total += b * cfg.padded_vocab * 4  # logits
    return bytes_total


def model_flops(cfg, shape, kind: str) -> float:
    """MODEL_FLOPS = 6*N*D (dense) / 6*N_active*D (MoE); D = tokens this step."""
    n = cfg.active_param_count()
    if kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens  # forward only
    tokens = shape.global_batch * 1
    return 2.0 * n * tokens  # decode: one token per sequence
