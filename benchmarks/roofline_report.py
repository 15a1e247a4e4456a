"""Aggregate the dry-run JSONs into the EXPERIMENTS.md roofline table.

    PYTHONPATH=src python -m benchmarks.roofline_report [--dir results/dryrun]
        [--markdown]
"""
from __future__ import annotations

import argparse
import glob
import json
import os


def load(dirname: str):
    recs = []
    for path in sorted(glob.glob(os.path.join(dirname, "*.json"))):
        with open(path) as f:
            recs.append(json.load(f))
    return recs


def fmt_row(r, md=False):
    rf = r.get("roofline", {})
    sep = " | " if md else ","
    cells = [
        r["arch"], r["shape"], r["mesh"],
        "SKIP" if r.get("skipped") else
        ("OK" if r["ok"] else "FAIL"),
        f"{r.get('compile_s', 0):.1f}",
        f"{r.get('mem_temp_gib', 0) + r.get('mem_args_gib', 0):.2f}",
        f"{rf.get('compute_s', 0):.4f}" if rf else "",
        f"{rf.get('memory_s', 0):.4f}" if rf else "",
        f"{rf.get('collective_s', 0):.4f}" if rf else "",
        rf.get("dominant", r.get("skip_reason", "")[:40]),
        f"{rf.get('useful_ratio', 0):.3f}" if rf else "",
        f"{rf.get('roofline_fraction', 0):.3f}" if rf else "",
    ]
    return sep.join(str(c) for c in cells)


HEADER = ["arch", "shape", "mesh", "status", "compile_s", "mem_GiB/dev",
          "compute_s", "memory_s", "collective_s", "dominant",
          "MODEL/HLO_flops", "roofline_frac"]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default="results/dryrun")
    ap.add_argument("--markdown", action="store_true")
    ap.add_argument("--mesh", default=None, choices=[None, "16x16", "2x16x16"])
    args = ap.parse_args()
    recs = load(args.dir)
    if args.mesh:
        recs = [r for r in recs if r["mesh"] == args.mesh]
    sep = " | " if args.markdown else ","
    print(sep.join(HEADER))
    if args.markdown:
        print(" | ".join("---" for _ in HEADER))
    n_ok = n_skip = n_fail = 0
    for r in recs:
        print(fmt_row(r, args.markdown))
        if r.get("skipped"):
            n_skip += 1
        elif r["ok"]:
            n_ok += 1
        else:
            n_fail += 1
    print(f"\n# {n_ok} compiled, {n_skip} documented skips, {n_fail} failed")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
