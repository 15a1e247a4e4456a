"""Fig. 9: heterogeneous accelerators — S2 (small, BW=16) and S4 (large,
BW=256) on Vision and Mix.  Validation: MAGMA best everywhere; AI-MT-like
(homogeneous-targeted) collapses on heterogeneous settings.

MAGMA batches per setting (scenarios sharing (G, A) stack): the two tasks
x all seeds of each setting run as one device-sharded ``repro.core.sweep``
grid."""
from __future__ import annotations

from benchmarks.common import (print_normalized, resolve,
                               run_problems_batched, std_parser,
                               summarize_vs)


def run(budget, methods, group_size=100, seeds=1):
    specs = [(f"{task}-{setting}-bw{int(bw)}", task, setting, bw)
             for setting, bw in (("S2", 16.0), ("S4", 256.0))
             for task in ("Vision", "Mix")]
    rows = run_problems_batched(specs, methods, budget, group_size, seeds)
    print_normalized("Fig 9: heterogeneous S2/S4", rows)
    vs = summarize_vs(rows)
    print("geomean MAGMA advantage:",
          {k: round(v, 3) for k, v in vs.items()})
    return rows


def main():
    args = std_parser(__doc__).parse_args()
    budget, methods = resolve(args)
    run(budget, methods, args.group_size, args.seeds)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
