"""Serve three tenant models with batched requests, scheduled by MAGMA.

    PYTHONPATH=src python examples/serve_multitenant.py

The engine decomposes requests into prefill/decode jobs, profiles each
(job x submesh) pair with the TPU cost model, searches the mapping with
MAGMA, prints the schedule + timeline against the manual baselines, and
then EXECUTES the schedule for real (greedy decoding on the smoke-size
models) to show end-to-end token generation.
"""
import numpy as np

from repro.launch.serve import build_tenants
from repro.serve.engine import MultiTenantEngine, default_submeshes


def main():
    tenants = build_tenants(["granite-3-2b", "qwen2-moe-a2.7b",
                             "falcon-mamba-7b"])
    engine = MultiTenantEngine(tenants, default_submeshes(), budget=2_000,
                               decode_window=8, seed=0)
    rng = np.random.default_rng(0)
    reqs = [(t.name, int(rng.integers(48, 128)), 16)
            for _ in range(4) for t in tenants]
    jobs = engine.jobs_for_requests(reqs)
    print(f"{len(reqs)} requests -> {len(jobs)} jobs "
          f"on {len(engine.submeshes)} submeshes\n")

    outs = {}
    for method in ("magma", "herald_like", "ai_mt_like"):
        outs[method] = engine.schedule(jobs, method=method)
        o = outs[method]
        print(f"{method:12s} makespan {o['makespan_s'] * 1e6:10.2f} us  "
              f"throughput {o['throughput_flops'] / 1e12:6.2f} TFLOP/s")

    best = outs["magma"]
    print("\nMAGMA submesh queues (job uids):")
    for sm, q in zip(engine.submeshes, best["queues"]):
        print(f"  {sm.name:8s} (tp={sm.tp:2d}): {q}")

    prompts = {j.uid: rng.integers(0, 256, (1, j.seq))
               for j in jobs if j.phase == "prefill"}
    gen = engine.execute(jobs, best["queues"], prompts)
    some = [j for j in jobs if j.phase == "decode"][0]
    print(f"\nexecuted {len(gen)} decode jobs; e.g. job {some.uid} "
          f"({some.tenant}) -> tokens {gen[some.uid][0, :8]}...")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
