"""``bench/run.py`` as the driver starts it: off the TPU, without the
system under test, and the shape of its last line."""
import importlib.util
import json
import os
import shutil
import subprocess
import sys

import jax  # noqa: F401  (read its configuration before main() sets the
#              cache variable, so this process keeps no persistent cache)
import pytest

from benchlib import cells

RUN = os.path.join(cells.BENCH, "run.py")
OFFLINE = cells.of_driver("offline")[0]
ARGS = ["--workload", OFFLINE, "--seed", "3", "--seconds", "1",
        "--trace", "0"]


def _env(**extra):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", **extra)
    return env


def test_exits_nonzero_with_no_result_off_the_tpu(tmp_path):
    out = subprocess.run([sys.executable, RUN] + ARGS, capture_output=True,
                         text=True, env=_env(), timeout=300, cwd=tmp_path)
    assert out.returncode == 3
    assert out.stdout == ""
    assert "TPU" in out.stderr


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(os.path.join(cells.REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(cells.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = subprocess.run([sys.executable, "bench/run.py"] + ARGS,
                         capture_output=True, text=True, env=_env(),
                         timeout=300, cwd=tmp_path)
    assert out.returncode != 0
    assert out.stdout == ""


def small_offline_cell():
    cell = cells.cell(OFFLINE)
    cell.traffic = dict(cell.traffic, mixes=["Lang", "Recom"],
                        bw_gb=[16, 64], seeds_per_scenario=2)
    return cell


@pytest.fixture
def run_module(monkeypatch, tmp_path):
    spec = importlib.util.spec_from_file_location("bench_run_main", RUN)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    # skip the look for a chip and the persistent cache; the rest of a
    # run is the benchmark's own
    monkeypatch.setattr(mod, "chips_ok", lambda cell: True)
    monkeypatch.setattr(mod, "use_cache", lambda: None)
    small = small_offline_cell()
    monkeypatch.setattr(cells, "cell", lambda name: small)
    monkeypatch.setattr(mod, "CACHE", str(tmp_path / "cache"))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "")
    return mod


def test_last_line_is_the_result_object(run_module, capsys):
    assert run_module.main(ARGS) == 0
    out, err = capsys.readouterr()
    result = json.loads(out.strip().splitlines()[-1])
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics",
                                "device"]
    assert list(result)[-1] == "compared"
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["attempted"] % 8 == 0
    assert result["failed"] == 0
    assert set(result["metrics"]) == {"setup_s", "evals_per_s"}
    for m in result["metrics"].values():
        assert m["value"] > 0 and m["unit"] in ("s", "evals/s")
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    assert set(result["compared"]) == {"missing", "fitness_gap",
                                       "mean_shortfall"}
    tail = err.strip().splitlines()[-3:]
    assert [t.split()[1] for t in tail] == ["missing", "fitness_gap",
                                           "mean_shortfall"]
    assert all(" limit " in t for t in tail)
