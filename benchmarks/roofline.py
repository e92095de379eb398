"""Roofline analysis over the dry-run artifacts (EXPERIMENTS.md §Roofline).

Per (arch x shape x mesh) cell, from experiments/dryrun/<cell>.json:

    compute term    = exec_FLOPs_per_device / peak_FLOP/s     (197e12 bf16)
    memory term     = exec_bytes_per_device / HBM_bw          (819e9 B/s)
    collective term = collective_bytes_per_device / link_bw   (50e9 B/s)

Methodology note (CPU-backend correction, documented in EXPERIMENTS.md):
``compiled.cost_analysis()`` on the CPU backend counts each while-loop BODY
once, not x trip count - scan-over-layers therefore undercounts FLOPs by
~n_groups (we measured useful-ratios >> 1 before correcting).  We therefore
compute the executed FLOPs analytically from the model geometry
(matmul-exact, attention/recurrence included, remat multiplicity applied)
and scale the HLO bytes/collective numbers by the same per-cell
multiplicity factor  scale = analytic_FLOPs / HLO_FLOPs  (the big loops
carry matmuls, HBM traffic and FSDP collectives with the same trip counts,
so one factor corrects all three to first order).  Raw HLO values are kept
as cross-check columns.

MODEL_FLOPS = 6*N_active*D (train) or 2*N_active*D (serve); the ratio
MODEL_FLOPS / exec_FLOPs measures how much of the compiled compute is
"useful" (remat + attention overhead push it below 1; full remat alone
costs ~0.75).
"""
from __future__ import annotations

import glob
import json
import os
from typing import Optional

from repro import configs
from repro.configs.base import SHAPES
from repro.core.hw import tpu_spec

DRYRUN_DIR = "experiments/dryrun"


def model_flops(arch: str, shape: str) -> float:
    cfg = configs.get_arch(arch)
    sh = SHAPES[shape]
    n_active = cfg.active_param_count()
    if sh.kind == "train":
        tokens = sh.tokens
        return 6.0 * n_active * tokens
    if sh.kind == "prefill":
        return 2.0 * n_active * sh.tokens
    # decode: one token per sequence
    return 2.0 * n_active * sh.global_batch


def analytic_flops(arch: str, shape: str, mode: str = "digital") -> float:
    """Executed FLOPs for one step, whole job (all chips), forward+backward
    with remat multiplicity.  Matmul-exact on the parameter path; attention
    and recurrences use their standard counts."""
    cfg = configs.get_arch(arch)
    sh = SHAPES[shape]
    if sh.kind == "train":
        d_tokens = sh.tokens
        s_kv = sh.seq_len
        mult = 4.0  # fwd + remat-fwd + 2x bwd (full per-group checkpoint)
    elif sh.kind == "prefill":
        d_tokens = sh.tokens
        s_kv = sh.seq_len
        mult = 1.0
    else:
        d_tokens = sh.global_batch
        s_kv = sh.seq_len
        mult = 1.0

    n_active = cfg.active_param_count()
    vocab_embed = cfg.vocab_size * cfg.d_model
    # parameter matmuls: every active param except the lookup embedding
    f = 2.0 * (n_active - vocab_embed) * d_tokens

    # attention: QK^T + AV, causal halves the prefill/train window
    n_attn = sum(
        1 for i in range(cfg.n_layers)
        if cfg.layer_kind(i) in ("attn_mlp", "attn_moe")
    )
    if cfg.attn_every:
        n_attn += cfg.n_layers // cfg.attn_every
    hd = cfg.hd
    if sh.kind == "decode":
        kv_per_q = s_kv
    else:
        kv_per_q = s_kv / 2.0
    f += n_attn * 4.0 * d_tokens * kv_per_q * cfg.n_heads * hd

    # recurrences (elementwise-matvec state updates)
    if cfg.block == "rwkv":
        hdh = cfg.d_model // cfg.n_heads
        f += cfg.n_layers * 6.0 * d_tokens * cfg.n_heads * hdh * hdh
    if cfg.block == "mamba":
        d_in = 2 * cfg.d_model
        f += cfg.n_layers * 6.0 * d_tokens * d_in * cfg.ssm_state

    if mode != "digital":
        # signed-split doubles the analog parameter-matmul passes
        f += 2.0 * (n_active - vocab_embed) * d_tokens
    return f * mult


def analyse_cell(path: str) -> Optional[dict]:
    with open(path) as f:
        r = json.load(f)
    hlo_flops_dev = float(r["cost"].get("flops") or 0.0)
    hlo_bytes_dev = float(r["cost"].get("bytes accessed") or 0.0)
    hlo_coll_dev = float(r["collectives"]["total_bytes"])
    chips = int(r["n_devices"])
    mode = r.get("mode", "digital")

    exec_flops = analytic_flops(r["arch"], r["shape"], mode)
    exec_flops_dev = exec_flops / chips
    # while-loop trip-count correction factor (see module docstring)
    scale = (exec_flops_dev / hlo_flops_dev) if hlo_flops_dev else 1.0
    scale = max(scale, 1.0)     # never scale below the raw HLO numbers
    bytes_dev = hlo_bytes_dev * scale
    coll_dev = hlo_coll_dev * scale

    chip = tpu_spec(r["target_device_kind"])
    t_c = exec_flops_dev / chip.peak_flops
    t_m = bytes_dev / chip.hbm_bw
    t_x = coll_dev / chip.ici_bw
    terms = {"compute": t_c, "memory": t_m, "collective": t_x}
    dominant = max(terms, key=terms.get)
    mf = model_flops(r["arch"], r["shape"])
    useful = mf / exec_flops if exec_flops else 0.0
    t_total = max(terms.values())
    if SHAPES[r["shape"]].kind == "decode":
        # decode is intrinsically memory-bound: the ideal step time is one
        # streaming read of (active params + cache) per chip
        cfg = configs.get_arch(r["arch"])
        ideal_bytes = (
            cfg.active_param_count() * (2 if cfg.param_dtype == "bfloat16"
                                        else 4)
            + r["memory"]["argument_size_in_bytes"] * chips * 0.5
        ) / chips
        t_ideal = ideal_bytes / chip.hbm_bw
    else:
        t_ideal = mf / chips / chip.peak_flops
    return {
        "arch": r["arch"],
        "shape": r["shape"],
        "mesh": r["mesh"],
        "mode": mode,
        "chips": chips,
        "compute_s": t_c,
        "memory_s": t_m,
        "collective_s": t_x,
        "dominant": dominant,
        "model_flops": mf,
        "exec_flops_dev": exec_flops_dev,
        "hlo_flops_dev": hlo_flops_dev,
        "loop_scale": scale,
        "useful_ratio": useful,
        "roofline_frac": t_ideal / t_total if t_total else 0.0,
        "args_gib": r["memory"]["argument_size_in_bytes"] / 2**30,
        "temp_gib": r["memory"]["temp_size_in_bytes"] / 2**30,
    }


def what_moves_it(row: dict) -> str:
    d = row["dominant"]
    if d == "compute":
        if row["useful_ratio"] < 0.5:
            return ("compute-bound with low useful ratio: cut remat/"
                    "redundant FLOPs (checkpoint policy, fused attention)")
        return "compute-bound near useful peak: only better MXU util helps"
    if d == "memory":
        return ("memory-bound: fuse/bf16-ify the largest intermediates, "
                "shrink cache dtype, better layouts")
    return ("collective-bound: reshard to cut all-gathers (FSDP prefetch "
            "grouping, SP boundaries), overlap collectives with compute")


def load_all(mesh: Optional[str] = None, mode: Optional[str] = None,
             include_tagged: bool = False):
    rows = []
    for path in sorted(glob.glob(os.path.join(DRYRUN_DIR, "*.json"))):
        with open(path) as f:
            if not include_tagged and json.load(f).get("tag"):
                continue  # §Perf hillclimb variants live in their own table
        row = analyse_cell(path)
        if row is None:
            continue
        if mesh and row["mesh"] != mesh:
            continue
        if mode and row["mode"] != mode:
            continue
        rows.append(row)
    return rows


def markdown_table(rows) -> str:
    hdr = ("| arch | shape | chips | compute s | memory s | coll s | "
           "dominant | useful | roofline |")
    sep = "|" + "---|" * 9
    lines = [hdr, sep]
    for r in rows:
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['chips']} "
            f"| {r['compute_s']:.3e} | {r['memory_s']:.3e} "
            f"| {r['collective_s']:.3e} | **{r['dominant']}** "
            f"| {r['useful_ratio']:.2f} | {r['roofline_frac']:.2f} |"
        )
    return "\n".join(lines)


def main() -> None:
    rows = load_all(mesh="single", mode="digital")
    if not rows:
        print("no dry-run artifacts found - run repro.launch.dryrun first")
        return
    print("\n== Roofline (single pod, 256 chips, digital mode) ==")
    print(markdown_table(rows))
    print("\nper-cell bottleneck guidance:")
    for r in rows:
        print(f"  {r['arch']:>26s}/{r['shape']:<12s}: {what_moves_it(r)}")
    worst = sorted(rows, key=lambda r: r["roofline_frac"])[:3]
    print("\nworst roofline fractions (hillclimb candidates): "
          + ", ".join(f"{r['arch']}/{r['shape']}={r['roofline_frac']:.2f}"
                      for r in worst))


if __name__ == "__main__":
    main()
