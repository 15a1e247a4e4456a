#!/usr/bin/env python3
"""Readings that the limits of the comparison are set from, on the chip.

    python3 bench/limits.py --workload <cell> --seeds 1,2,... \
        [--fault-seeds 7,8,9] [--seconds S] [--fault-seconds F]

For each seed it runs the cell's window at the cell's own size and load
and reads the compared numbers twice: as the system reported its answers
(the sound reading), and with the reported fitness replaced by the
reference's own in bfloat16 (the control, the next precision below the
configuration's float32).  With ``--fault-seeds`` it then plants the
``unchanged`` fault (``benchlib.faults``) and reads the numbers again.
Prints one line per run and a JSON summary: the lower reading (largest
sound), and the smallest control and fault readings, per number.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--fault-seconds", type=float, default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [BENCH, os.path.join(REPO, "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(REPO, ".jax_cache")
    from benchlib import cells, check, faults, runner
    bench = cells.benchmark()
    cell = cells.cell(args.workload, bench)
    seconds = args.seconds or bench["run_seconds"]
    import jax
    if jax.devices()[0].platform != "tpu":
        print("limits.py reads on the chip; no TPU found", file=sys.stderr)
        return 3
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    bf16 = check.control_dtype("bfloat16")

    def read(seed, secs):
        with tempfile.TemporaryDirectory() as d:
            m = runner.measure(cell, seed, secs, False, time.perf_counter(),
                               d)
        judge = check.Judge()
        return (m, judge.compare(m.run.answers, m.run.due),
                judge.compare(m.run.answers, m.run.due, control_dtype=bf16))

    rows = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        m, sound, control = read(seed, seconds)
        rows.append({"kind": "sound", "seed": seed, "due": m.run.due,
                     "numbers": sound})
        rows.append({"kind": "control", "seed": seed, "due": m.run.due,
                     "numbers": control})
        print(json.dumps(rows[-2]), json.dumps(rows[-1]), flush=True)
    if args.fault_seeds:
        with faults.planted("unchanged"):
            for seed in [int(s) for s in args.fault_seeds.split(",")]:
                m, sound, _ = read(seed, args.fault_seconds or seconds)
                rows.append({"kind": "fault:unchanged", "seed": seed,
                             "due": m.run.due, "numbers": sound})
                print(json.dumps(rows[-1]), flush=True)
    summary = {}
    for kind, pick in (("sound", max), ("control", min),
                       ("fault:unchanged", min)):
        got = [r["numbers"] for r in rows if r["kind"] == kind]
        if got:
            summary[kind] = {k: pick(g[k] for g in got)
                             for k in check.NUMBERS}
    print(json.dumps({"workload": args.workload, "summary": summary}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
