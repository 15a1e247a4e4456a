"""Fig. 8: small homogeneous accelerator (S1, BW=16 GB/s), four tasks,
all mappers.  Validation: MAGMA >= every baseline (paper: geomean 1.4x
over Herald-like / 1.41x over AI-MT-like, 1.6x over other optimizers).

MAGMA runs all four tasks x all seeds as one ``repro.core.sweep`` grid
(the tables share (G, A)), sharded across however many devices are
visible."""
from __future__ import annotations

from benchmarks.common import (print_normalized, resolve,
                               run_problems_batched, std_parser,
                               summarize_vs)


def run(budget, methods, group_size=100, seeds=1):
    specs = [(task, task, "S1", 16.0)
             for task in ("Vision", "Lang", "Recom", "Mix")]
    rows = run_problems_batched(specs, methods, budget, group_size, seeds)
    print_normalized("Fig 8: S1 homogeneous, BW=16 GB/s", rows)
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
