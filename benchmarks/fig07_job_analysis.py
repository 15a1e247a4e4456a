"""Fig. 7: per-job no-stall latency and required BW across tasks and
dataflow styles (HB vs LB)."""
from __future__ import annotations

import numpy as np

from repro.costmodel import MaestroModel, SubAccelConfig
from repro.workloads import build_task_groups
from repro.workloads.models import TASK_MODELS, model_layers

HB = SubAccelConfig("hb64", pe_h=64, dataflow="HB", sg_bytes=291 * 1024)
LB = SubAccelConfig("lb64", pe_h=64, dataflow="LB", sg_bytes=218 * 1024)


def run(verbose: bool = True):
    model = MaestroModel()
    rows = {}
    print("model,task,lat_HB_s,lat_LB_s,bw_HB_GBs,bw_LB_GBs")
    for task in ("Vision", "Lang", "Recom"):
        for name in TASK_MODELS[task][:3]:
            layers = model_layers(name)
            prof_h = [model.profile(l, HB) for l in layers]
            prof_l = [model.profile(l, LB) for l in layers]
            row = (np.mean([p.no_stall_latency_s for p in prof_h]),
                   np.mean([p.no_stall_latency_s for p in prof_l]),
                   np.mean([p.required_bw for p in prof_h]) / 2**30,
                   np.mean([p.required_bw for p in prof_l]) / 2**30)
            rows[name] = row
            print(f"{name},{task},{row[0]:.3e},{row[1]:.3e},"
                  f"{row[2]:.3f},{row[3]:.3f}")
    print("\ntask_avg,lat_HB_s,bw_HB_GBs  (paper: Vision max lat/min BW, "
          "Recom the reverse; LB slower but leaner)")
    stats = {}
    for task in ("Vision", "Lang", "Recom"):
        g = build_task_groups(task, group_size=60, seed=0)[0]
        lat = np.mean([model.profile(j.layer, HB).no_stall_latency_s
                       for j in g.jobs])
        bw = np.mean([model.profile(j.layer, HB).required_bw
                      for j in g.jobs]) / 2**30
        stats[task] = (lat, bw)
        print(f"{task},{lat:.3e},{bw:.3f}")
    assert stats["Vision"][0] > stats["Lang"][0] > stats["Recom"][0]
    assert stats["Recom"][1] > stats["Lang"][1] > stats["Vision"][1]
    return stats


def main():
    run()


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
