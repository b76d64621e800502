"""§Roofline: aggregate the dry-run artifacts into the per-cell roofline table.

Reads experiments/dryrun/*.json (written by repro.launch.dryrun) and prints the
three terms, the dominant bottleneck, MODEL_FLOPS/HLO_FLOPs, and a one-line
"what would move the dominant term" note per (arch x shape x mesh)."""
from __future__ import annotations

import glob
import json
import os
import time

from benchmarks.common import emit

NOTES = {
    ("memory_s", "train"): "chunked/flash attention kills S^2 softmax HBM traffic",
    ("memory_s", "decode"): "paged+quantized KV; fuse gather into attention kernel",
    ("memory_s", "prefill"): "chunked attention + bf16 logits; larger fusion blocks",
    ("collective_s", "train"): "seq-parallel resid (AR -> RS+AG) + overlap w/ compute",
    ("collective_s", "decode"): "shard KV heads not batch; duplicate small params",
    ("collective_s", "prefill"): "overlap all-gather with per-layer compute (async)",
    ("compute_s", "train"): "already MXU-bound: raise per-chip batch or quantize",
    ("compute_s", "decode"): "batch more sequences per chip (decode is latency-bound)",
    ("compute_s", "prefill"): "already MXU-bound: good roofline position",
}


def load_cells(out_dir: str = "experiments/dryrun", tag: str = ""):
    cells = []
    for f in sorted(glob.glob(os.path.join(out_dir, "*.json"))):
        with open(f) as fh:
            c = json.load(fh)
        if (c.get("tag") or "") != tag:
            continue
        cells.append(c)
    return cells


def rows_from_cells(cells):
    from repro.configs import get_config, get_shape
    from repro.launch.hlo_analysis import decode_bytes_global, peaks

    rows = []
    for c in cells:
        r = dict(c.get("roofline", {}))
        if c["kind"] == "decode" and "error" not in r:
            # correct the HloCostAnalysis DUS full-buffer artifact (§Roofline)
            cfg = get_config(c["arch"])
            shape = get_shape(c["shape"])
            hbm_bw = peaks(c["device_kind"])["hbm_bw"]
            mem_corr = decode_bytes_global(cfg, shape) / c["chips"] / hbm_bw
            r["memory_s"] = mem_corr
            bound = max(r["compute_s"], mem_corr, r["collective_s"])
            r["dominant"] = max(
                ("compute_s", "memory_s", "collective_s"),
                key=lambda k: r[k],
            )
            r["roofline_fraction"] = r["compute_s"] / bound if bound else 0.0
        dom = r.get("dominant", "?")
        rows.append({
            "arch": c["arch"],
            "shape": c["shape"],
            "mesh": c["mesh"],
            "kind": c["kind"],
            "compute_s": round(r.get("compute_s", 0), 5),
            "memory_s": round(r.get("memory_s", 0), 5),
            "collective_s": round(r.get("collective_s", 0), 5),
            "dominant": dom,
            "roofline_fraction": round(r.get("roofline_fraction", 0), 4),
            "model_flops": f"{c.get('model_flops', 0):.3e}",
            "useful_flops_ratio": round(c.get("useful_flops_ratio", 0), 4),
            "bytes_per_device": c.get("memory", {}).get("peak_bytes_per_device", 0),
            "note": NOTES.get((dom, c["kind"]), ""),
        })
    return rows


def run():
    t0 = time.time()
    rows = rows_from_cells(load_cells())
    frac = [r["roofline_fraction"] for r in rows if r["mesh"] == "16x16"]
    avg = sum(frac) / max(len(frac), 1)
    emit("roofline", rows, t0, f"cells={len(rows)};avg_fraction_single_pod={avg:.3f}")
    return rows


if __name__ == "__main__":
    run()
