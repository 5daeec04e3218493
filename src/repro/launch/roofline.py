"""Roofline analysis from dry-run artifacts (EXPERIMENTS.md §Roofline).

Three terms per (arch x shape) on the single-pod mesh (256 chips):

    t_compute    = HLO_FLOPs / (chips * 197 TF/s)
    t_memory     = HLO_bytes / (chips * 819 GB/s)
    t_collective = collective_bytes_per_device / 50 GB/s-per-link

HLO FLOPs/bytes come from the 1/2-block probe extrapolation (cost_analysis
counts scan bodies once — verified in-container).  cost_analysis on the CPU
backend reports *global* (all-partition) FLOPs for the SPMD program, so the
per-chip share divides by the chip count; collective bytes are parsed from
the probe HLO (result shapes of all-reduce/all-gather/reduce-scatter/
all-to-all/collective-permute), which is already per-device.

MODEL_FLOPS (analytic useful work):
    train:   6 * N_active * tokens  + attention term
    prefill: 2 * N_active * tokens  + attention term
    decode:  2 * N_active * batch   + KV-read term (memory side)
"""
from __future__ import annotations

import json

from repro.configs.archs import ARCHS, SHAPES
from repro.launch.mesh import PRODUCTION_KIND, device_peaks
from repro.models.common import ModelConfig, padded_vocab

CHIPS = 256  # single-pod roofline mesh


def param_counts(cfg: ModelConfig) -> tuple[float, float]:
    """(total, active-per-token) parameter counts."""
    D = cfg.d_model
    hd = cfg.hd if cfg.num_heads else 0  # attn-free archs (mamba2)
    embed = padded_vocab(cfg.vocab_size) * D * (1 if cfg.tie_embeddings else 2)
    total = embed
    active = embed
    specs = list(cfg.pattern) * cfg.num_blocks + list(cfg.tail)
    for spec in specs:
        if spec.kind in ("global", "local"):
            attn = D * hd * (cfg.num_heads + 2 * cfg.num_kv_heads) \
                + cfg.num_heads * hd * D
            total += attn
            active += attn
        elif spec.kind == "rglru":
            r = D * cfg.rglru_width * 2 + 7 * cfg.rglru_width
            total += r
            active += r
        elif spec.kind == "ssd":
            from repro.models.recurrent import ssd_dims
            H, P, N = ssd_dims(cfg)
            r = D * (2 * H * P + 2 * N + H) + H * P * D + H * P
            total += r
            active += r
        if cfg.is_moe:
            per_exp = 3 * D * cfg.moe_d_ff
            total += cfg.num_experts * per_exp + D * cfg.num_experts
            active += cfg.num_experts_per_tok * per_exp + D * cfg.num_experts
        elif cfg.d_ff:
            m = 3 * D * cfg.d_ff
            total += m
            active += m
        if cfg.encoder_layers:  # cross attention in decoder layers
            c = 2 * D * hd * (cfg.num_heads + cfg.num_kv_heads)
            total += c
            active += c
    if cfg.encoder_layers:
        enc = cfg.encoder_layers * (
            D * hd * (cfg.num_heads + 2 * cfg.num_kv_heads)
            + cfg.num_heads * hd * D + 3 * D * cfg.d_ff)
        total += enc
        active += enc
    return float(total), float(active)


def model_flops(arch: str, shape: str) -> float:
    """Analytic useful FLOPs for one step of this cell."""
    cfg = ARCHS[arch]
    sh = SHAPES[shape]
    B, S = sh["global_batch"], sh["seq_len"]
    total, active = param_counts(cfg)
    specs = list(cfg.pattern) * cfg.num_blocks + list(cfg.tail)

    if sh["kind"] == "train":
        tokens = B * S
        flops = 6.0 * active * tokens
        # attention scores+values: 12 * B * S * S_eff * H * hd per attn layer
        for spec in specs:
            if spec.kind in ("global", "local"):
                s_eff = min(spec.window or S, S) if spec.kind == "local" else S
                flops += 12.0 * B * S * (s_eff / 2 if spec.kind != "local"
                                         else s_eff) * cfg.num_heads * cfg.hd
        return flops
    if sh["kind"] == "prefill":
        tokens = B * S
        flops = 2.0 * active * tokens
        for spec in specs:
            if spec.kind in ("global", "local"):
                s_eff = min(spec.window or S, S) if spec.kind == "local" else S
                flops += 4.0 * B * S * (s_eff / 2 if spec.kind != "local"
                                        else s_eff) * cfg.num_heads * cfg.hd
        return flops
    # decode: one token per sequence
    flops = 2.0 * active * B
    for spec in specs:
        if spec.kind in ("global", "local"):
            s_eff = min(spec.window or S, S) if spec.kind == "local" else S
            flops += 4.0 * B * s_eff * cfg.num_heads * cfg.hd
    return flops


def analyze_cell(cell: dict) -> dict:
    """cell = one dry-run record with 'costs' (probe-extrapolated).

    cost_analysis() of the compiled SPMD module reports the **per-device**
    program's FLOPs/bytes (verified in-container with a sharded matmul), so
    the three terms are per-chip directly; MODEL_FLOPS is global and divides
    by the chip count for comparisons.
    """
    costs = cell["costs"]
    flops = costs["hlo_flops"]          # per device
    bytes_ = costs["hlo_bytes"]         # per device
    coll = sum(costs["coll_bytes"].values())  # per device
    peaks = device_peaks(PRODUCTION_KIND)
    t_compute = flops / peaks["flops_bf16"]
    t_memory = bytes_ / peaks["hbm_bw"]
    t_collective = coll / peaks["ici_bw"]
    terms = dict(compute=t_compute, memory=t_memory, collective=t_collective)
    bound = max(terms, key=terms.get)
    mf = model_flops(cell["arch"], cell["shape"])
    return dict(
        t_compute=t_compute, t_memory=t_memory, t_collective=t_collective,
        bound=bound, model_flops=mf,
        useful_ratio=mf / max(flops * CHIPS, 1.0),
        step_time=max(terms.values()),
        mfu=mf / CHIPS / peaks["flops_bf16"] / max(terms.values()),
    )


def render_table(path: str) -> str:
    with open(path) as f:
        cells = json.load(f)
    rows = ["| arch | shape | compute s | memory s | collective s | bound | "
            "MODEL/HLO | roofline MFU |",
            "|---|---|---|---|---|---|---|---|"]
    for c in cells:
        if c.get("status") != "ok" or "costs" not in c:
            continue
        r = analyze_cell(c)
        rows.append(
            f"| {c['arch']} | {c['shape']} | {r['t_compute']:.2e} | "
            f"{r['t_memory']:.2e} | {r['t_collective']:.2e} | {r['bound']} | "
            f"{r['useful_ratio']:.2f} | {r['mfu']:.1%} |")
    return "\n".join(rows)


if __name__ == "__main__":
    import sys
    print(render_table(sys.argv[1] if len(sys.argv) > 1
                       else "results/dryrun_optimized.json"))
