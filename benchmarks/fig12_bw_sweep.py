"""Fig. 12: BW sweep on heterogeneous S2 (small) and S4 (large), Mix task.
Validation: MAGMA's relative advantage grows as BW shrinks."""
from __future__ import annotations

from benchmarks.common import (print_normalized, resolve, run_problem,
                               std_parser, summarize_vs)


def run(budget, methods, group_size=100, seeds=1):
    rows = {}
    for setting, bws in (("S2", (1.0, 4.0, 16.0)),
                         ("S4", (1.0, 16.0, 256.0))):
        for bw in bws:
            rows[f"{setting}-bw{bw:g}"] = run_problem(
                "Mix", setting, bw, methods, budget, group_size, seeds)
    print_normalized("Fig 12: BW sweep (Mix)", rows)
    # advantage at the tightest vs loosest BW
    adv = {}
    for setting, lo, hi in (("S2", "S2-bw1", "S2-bw16"),
                            ("S4", "S4-bw1", "S4-bw256")):
        v_lo = summarize_vs({lo: rows[lo]})
        v_hi = summarize_vs({hi: rows[hi]})
        import numpy as np
        adv[setting] = (float(np.mean(list(v_lo.values()))),
                        float(np.mean(list(v_hi.values()))))
        print(f"{setting}: mean advantage at tight BW {adv[setting][0]:.2f}x"
              f" vs loose BW {adv[setting][1]:.2f}x")
    return rows


def main():
    args = std_parser(__doc__).parse_args()
    budget, methods = resolve(args)
    run(budget, methods, args.group_size, args.seeds)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
