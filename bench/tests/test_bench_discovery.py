"""Configurations, traffic, drivers and metrics are found by name."""
import json
import os
import shutil

import pytest

from benchlib import cells

SPEC = cells.benchmark()


def test_every_name_in_benchmark_json_has_its_file():
    for c in SPEC["configs"]:
        assert os.path.isfile(os.path.join(cells.REPO, c["file"]))
        assert cells.config(c["name"])["settings"]
    for w in SPEC["workloads"]:
        t = cells.traffic(w["traffic"])
        assert callable(cells.driver(t["driver"]).Session)
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert callable(cells.metric(m["name"]).read)


@pytest.mark.parametrize("kind,fn", [("configs", cells.config),
                                     ("traffic", cells.traffic),
                                     ("drivers", cells.driver),
                                     ("metrics", cells.metric)])
def test_a_missing_name_is_an_error(kind, fn):
    with pytest.raises(cells.UnknownName, match="no-such-name"):
        fn("no-such-name")


def test_unknown_workload_is_an_error():
    with pytest.raises(cells.UnknownName, match="nope"):
        cells.cell("nope", SPEC)


def test_a_new_traffic_file_is_picked_up_without_edits(tmp_path):
    bench = tmp_path / "bench"
    shutil.copytree(cells.BENCH, bench,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    dummy = {"driver": "served", "why": "dummy", "arrival": "poisson",
             "rate_hz": 1.5, "mixes": ["Lang"], "bw_gb": [16]}
    (bench / "traffic" / "dummy-poisson.json").write_text(json.dumps(dummy))
    spec = json.loads(json.dumps(SPEC))
    spec["workloads"].append({"name": "s4.served.dummy",
                              "config": "magma-s4-g100",
                              "traffic": "dummy-poisson", "chips": 1,
                              "why": "dummy"})
    cell = cells.cell("s4.served.dummy", spec, str(bench))
    assert cell.traffic == dummy
    assert cell.config == cells.config("magma-s4-g100")
    # a cell that names no metric list reports those of its driver's kind
    # only where the metric's workloads list names it
    assert [m["name"] for m in cell.end_to_end] == ["setup_s"]
    assert cell.per_layer == []


@pytest.mark.parametrize("name,e2e", [
    ("s4.offline.grid", {"setup_s", "evals_per_s"}),
    ("table3.served.poisson", {"setup_s", "schedules_per_s"}),
])
def test_each_cell_reports_setup_another_e2e_and_a_layer(name, e2e):
    cell = cells.cell(name, SPEC)
    assert {m["name"] for m in cell.end_to_end} == e2e
    moved = {m["moves"] for m in cell.per_layer}
    assert cell.per_layer and moved <= e2e


def test_contract_shape_of_benchmark_json():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(names) == len(set(names))
    assert [w["name"] for w in SPEC["workloads"]] == [
        "s4.offline.grid", "table3.served.poisson"]
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert m["moves"] in {e["name"] for e in SPEC["end_to_end"]}
    for c in SPEC["configs"]:
        assert cells.config(c["name"])["reduced"] == c["reduced"]
