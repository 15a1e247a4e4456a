#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic and metrics are looked up by name
(``BENCHMARK.json``, ``bench/configs``, ``bench/traffic``,
``bench/metrics``).  Set-up (imports, analysis, compilation or loading
from the persistent cache, warm-up) is timed from process start; then the
window runs for ``--seconds``.  With ``--trace 0`` the result carries the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics from a
profiled sub-window.  Every run compares its answers with the plain
reference and prints each compared number beside its limit, last on
standard error and last in the result.  The last line of standard output
is the result, one JSON object.

Without a TPU, or with fewer chips than the cell asks for, it exits 3 and
prints no result.  JAX's persistent compilation cache lives at the fixed
``<checkout>/.jax_cache``.
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
CACHE = os.path.join(REPO, ".jax_cache")


def say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def chips_ok(cell) -> bool:
    """Whether JAX finds a TPU with at least the chips the cell asks for."""
    import jax
    devs = jax.devices()
    if devs[0].platform == "tpu" and len(devs) >= cell.chips:
        return True
    say(f"needs {cell.chips} TPU chip(s); JAX found {len(devs)} "
        f"{devs[0].platform} device(s)")
    return False


def use_cache() -> None:
    """The persistent compilation cache at the fixed in-checkout path;
    programs of any compile time are kept, and none is evicted, so warm
    set-up loads them all."""
    import jax
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)


def main(argv=None) -> int:
    args = parse(argv)
    src = os.path.join(REPO, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        say(f"no system under test: {src}/repro is missing")
        return 2
    sys.path[:0] = [p for p in (BENCH, src) if p not in sys.path]
    from benchlib import cells
    cell = cells.cell(args.workload)
    # set before JAX first reads its configuration
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE
    import jax  # noqa: F401
    marks = {"jax_imported_s": time.perf_counter() - T_START}
    if not chips_ok(cell):
        return 3
    use_cache()
    marks["jax_ready_s"] = time.perf_counter() - T_START

    from benchlib import check, runner
    with tempfile.TemporaryDirectory(prefix="bench-profile-") as log_dir:
        result = runner.run_cell(cell, args.seed, args.seconds,
                                 bool(args.trace), T_START, log_dir, say=say)
    result["setup_parts"].update(marks)
    numbers = {k: v["value"] for k, v in result["compared"].items()}
    limits = {k: v["limit"] for k, v in result["compared"].items()}
    for line in check.lines(numbers, limits):
        say(line)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
