"""§Perf hillclimbing driver: hypothesis -> change -> re-lower -> record.

Three cells (selection rationale in EXPERIMENTS.md §Perf):
  1. moonshot-v1-16b-a3b x decode_32k   — worst MODEL/HLO FLOPs ratio
     (0.27): per-row capacity MoE dispatch computes every expert for every
     token at S=1.  Change: token-grouped decode routing (+ tight capacity).
  2. granite-3-2b x train_4k            — worst train roofline fraction:
     the f32 (B,S,V) logits pipeline and the S^2-free but still f32-heavy
     attention dominate HBM.  Change: fused (seq-chunked) cross-entropy,
     then gradient-accumulation microbatching for the temp footprint.
  3. zamba2-1.2b x train_4k             — most collective-bound train cell:
     FSDP all-gathers of a 1.2B-param model that would fit replicated.
     Change: fsdp=False (weights replicated over 'data'; grads still
     reduce across it) + fused CE.

Each variant re-runs the full dry-run cell (compile + unrolled-FLOPs
lowering) on the single-pod mesh and prints the three roofline terms.

    PYTHONPATH=src python -m benchmarks.perf_hillclimb [--cell NAME]
"""
import argparse
import json
import os

from repro.configs import get_config
from repro.launch.dryrun import run_cell
from repro.train.loop import TrainConfig


def variants_for(cell: str):
    if cell == "moonshot_decode":
        arch, shape = "moonshot-v1-16b-a3b", "decode_32k"
        cfg = get_config(arch)
        return arch, shape, [
            ("baseline (paper-style per-row capacity)", cfg, None),
            ("moe_group_decode", cfg.replace(moe_group_decode=True), None),
            ("moe_group_decode+cf1.0",
             cfg.replace(moe_group_decode=True, capacity_factor=1.0), None),
        ]
    if cell == "granite_train":
        arch, shape = "granite-3-2b", "train_4k"
        cfg = get_config(arch)
        return arch, shape, [
            ("baseline", cfg, None),
            ("fused_ce", cfg.replace(ce_seq_chunk=512), None),
            ("fused_ce+microbatch4", cfg.replace(ce_seq_chunk=512),
             TrainConfig(microbatches=4)),
            ("fused_ce+mb4+no_fsdp",
             cfg.replace(ce_seq_chunk=512, fsdp=False),
             TrainConfig(microbatches=4)),
        ]
    if cell == "zamba_train":
        arch, shape = "zamba2-1.2b", "train_4k"
        cfg = get_config(arch)
        return arch, shape, [
            ("baseline (FSDP, per-step scan)", cfg, None),
            # refuted hypothesis kept for the record: FSDP all-gathers were
            # NOT the bottleneck (collective term barely moved)
            ("no_fsdp", cfg.replace(fsdp=False), None),
            ("ssm_time_chunk64",
             cfg.replace(ssm_time_chunk=64), None),
            ("time_chunk64+fused_ce+mb2",
             cfg.replace(ssm_time_chunk=64, ce_seq_chunk=512),
             TrainConfig(microbatches=2)),
        ]
    if cell == "falcon_train":
        arch, shape = "falcon-mamba-7b", "train_4k"
        cfg = get_config(arch)
        return arch, shape, [
            ("baseline (per-step time scan)", cfg, None),
            ("ssm_time_chunk16", cfg.replace(ssm_time_chunk=16), None),
            ("ssm_time_chunk64", cfg.replace(ssm_time_chunk=64), None),
            ("time_chunk16+fused_ce+no_fsdp... ",
             cfg.replace(ssm_time_chunk=16, ce_seq_chunk=512), None),
        ]
    if cell == "phi3_train":
        arch, shape = "phi3-medium-14b", "train_4k"
        cfg = get_config(arch)
        return arch, shape, [
            ("baseline (head_dim contraction TP)", cfg, None),
            ("attn_batch_shard",
             cfg.replace(attn_batch_shard=True), None),
            ("attn_batch+fused_ce",
             cfg.replace(attn_batch_shard=True, ce_seq_chunk=512), None),
            ("attn_batch+fused_ce+mb4",
             cfg.replace(attn_batch_shard=True, ce_seq_chunk=512),
             TrainConfig(microbatches=4)),
        ]
    raise ValueError(cell)


CELLS = ("moonshot_decode", "phi3_train", "granite_train", "zamba_train",
         "falcon_train")


def run_one(cell: str, outdir: str):
    arch, shape, variants = variants_for(cell)
    print(f"\n==== hillclimb: {cell} ({arch} x {shape}) ====")
    print(f"{'variant':34s} {'compute_s':>9s} {'memory_s':>9s} "
          f"{'collect_s':>9s} {'step_s':>8s} {'temp_GiB':>8s} "
          f"{'MODEL/HLO':>9s} {'frac':>6s}")
    recs = []
    for name, cfg, tc in variants:
        rec = run_cell(arch, shape, multi_pod=False, cfg_override=cfg,
                       train_config=tc, verbose=False)
        rec["variant"] = name
        recs.append(rec)
        r = rec.get("roofline", {})
        if rec["ok"] and r:
            print(f"{name:34s} {r['compute_s']:9.4f} {r['memory_s']:9.4f} "
                  f"{r['collective_s']:9.4f} {r['step_time_s']:8.4f} "
                  f"{rec['mem_temp_gib']:8.2f} {r['useful_ratio']:9.3f} "
                  f"{r['roofline_fraction']:6.3f}")
        else:
            print(f"{name:34s} FAILED: {rec.get('error')}")
    os.makedirs(outdir, exist_ok=True)
    with open(os.path.join(outdir, f"{cell}.json"), "w") as f:
        json.dump(recs, f, indent=1)
    return recs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", choices=CELLS, default=None)
    ap.add_argument("--outdir", default="results/hillclimb")
    args = ap.parse_args()
    for cell in ([args.cell] if args.cell else CELLS):
        run_one(cell, args.outdir)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
