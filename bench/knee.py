#!/usr/bin/env python3
"""Rate sweep that sets a served cell's fixed offered rate, on the chip.

    python3 bench/knee.py --workload <served cell> --rates 2,2.5,3 \
        [--realisations 0,1,2] [--seconds S] [--seed N]

Sets the cell's service up once, then runs one window at each offered
rate (the traffic file's ``rate_hz`` replaced) for each realisation of
the arrival times (realisation 0 is the one every cell run offers), and
prints one JSON line per window and a table: requests offered, routed by
the window's end, the backlog at the window's end (requests due by then
and not yet routed), the latency median and 90th percentile, schedules
per second, real rows per batch and the share of batches with more than
one row.  A window keeps up when its backlog at the window's end is no
more than the pipeline holds in flight (``max_inflight x batch_rows``);
the knee is the highest rate at which every realisation keeps up, and
the cell offers 0.8 x the knee.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH)


def row(rate, realisation, run, cell) -> dict:
    from benchlib.stats import latencies, mean, percentile
    T = run.seconds
    reqs = run.requests
    done = [r["done_s"] for r in reqs if r["done_s"] is not None]
    backlog = sum(1 for r in reqs if r["done_s"] is None or r["done_s"] > T)
    stream = cell.config["stream"]
    lat = latencies(reqs)
    return {"rate_hz": rate, "realisation": realisation,
            "offered": len(reqs),
            "routed_by_end": sum(1 for d in done if d <= T),
            "backlog_at_end": backlog,
            "p50_s": percentile(lat, 50), "p90_s": percentile(lat, 90),
            "schedules_per_s": len(done) / max(done) if done else None,
            "rows_per_batch": mean(run.batches),
            "multi_row_share": mean([b > 1 for b in run.batches]),
            "keeps_up": backlog <= stream["max_inflight"]
            * stream["batch_rows"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--realisations", default="0")
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path[:0] = [BENCH, os.path.join(REPO, "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(REPO, ".jax_cache")
    from benchlib import cells, traffic
    bench = cells.benchmark()
    cell = cells.cell(args.workload, bench)
    if cell.traffic["driver"] != "served":
        print("knee.py sweeps served cells only", file=sys.stderr)
        return 2
    seconds = args.seconds or bench["run_seconds"]
    import jax
    if jax.devices()[0].platform != "tpu":
        print("knee.py measures on the chip; no TPU found", file=sys.stderr)
        return 3
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    rates = [float(r) for r in args.rates.split(",")]
    realisations = [int(k) for k in args.realisations.split(",")]
    session = cells.driver("served").Session(cell, args.seed, seconds,
                                             False, None)
    rows = []
    try:
        session.setup()
        print(json.dumps({"setup_s": time.perf_counter() - T_START}),
              flush=True)
        for rate in rates:
            mix = dict(cell.traffic, rate_hz=rate)
            for k in realisations:
                session.offer(traffic.requests(mix, cell.config, args.seed,
                                               seconds, k))
                rows.append(row(rate, k, session.window(), cell))
                print(json.dumps(rows[-1]), flush=True)
    finally:
        session.close()
    knee = None
    for rate in rates:
        if all(r["keeps_up"] for r in rows if r["rate_hz"] == rate):
            knee = rate
        else:
            break
    print("| rate/s | realisation | offered | backlog at end | p50 s | "
          "p90 s | schedules/s | rows/batch | multi-row batches | "
          "keeps up |")
    print("| --- | --- | --- | --- | --- | --- | --- | --- | --- | --- |")
    for r in rows:
        print(f"| {r['rate_hz']} | {r['realisation']} | {r['offered']} | "
              f"{r['backlog_at_end']} | {r['p50_s']:.3f} | {r['p90_s']:.3f} "
              f"| {r['schedules_per_s']:.3f} | {r['rows_per_batch']:.2f} | "
              f"{r['multi_row_share']:.2f} | "
              f"{'yes' if r['keeps_up'] else 'no'} |")
    print(json.dumps({"workload": args.workload, "knee_hz": knee,
                      "offer_hz": None if knee is None else 0.8 * knee}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
