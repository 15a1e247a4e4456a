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


def test_cells_are_found_by_the_driver_of_their_traffic():
    for kind in ("offline", "served"):
        names = cells.of_driver(kind, SPEC)
        assert names
        for name in names:
            assert cells.cell(name, SPEC).traffic["driver"] == kind
    assert cells.of_driver("no-such-driver", SPEC) == []


def test_unknown_workload_is_an_error():
    with pytest.raises(cells.UnknownName, match="nope"):
        cells.cell("nope", SPEC)


def test_a_new_traffic_file_is_picked_up_without_edits(tmp_path):
    spec, bench = with_added_cells(tmp_path, [1])
    cell = cells.cell("added.0", spec, bench)
    with open(os.path.join(bench, "traffic", "added-0.json")) as f:
        assert cell.traffic == json.load(f)
    assert cell.config == cells.config(SPEC["configs"][0]["name"])
    # a cell that names no metric list reports those of its driver's kind
    # only where the metric's workloads list names it
    assert [m["name"] for m in cell.end_to_end] == ["setup_s"]
    assert cell.per_layer == []


@pytest.mark.parametrize("name", [w["name"] for w in SPEC["workloads"]])
def test_each_cell_reports_setup_another_e2e_and_a_layer(name):
    cell = cells.cell(name, SPEC)
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    moved = {m["moves"] for m in cell.per_layer}
    assert cell.per_layer and moved <= e2e


CONTRACT_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
                 "end_to_end", "per_layer"}


def contract_faults(spec, bench=cells.BENCH):
    """What in ``spec`` (a ``BENCHMARK.json``) breaks the benchmark's
    contract or names a file ``bench`` lacks: one message each, none when
    all holds.  It checks properties, not names, so a later cell is added
    with no edit here."""
    faults = []
    if set(spec) != CONTRACT_KEYS:
        faults.append(f"top-level keys {sorted(spec)}")
    for kind in ("configs", "workloads"):
        names = [e["name"] for e in spec[kind]]
        if len(names) != len(set(names)):
            faults.append(f"{kind}: a name repeats in {names}")
    metrics = spec["end_to_end"] + spec["per_layer"]
    names = [m["name"] for m in metrics]
    if len(names) != len(set(names)):
        faults.append(f"metrics: a name repeats in {names}")
    configs = {c["name"] for c in spec["configs"]}
    for w in spec["workloads"]:
        if w["config"] not in configs:
            faults.append(f"{w['name']}: config {w['config']!r} not listed")
        try:
            cells.driver(cells.traffic(w["traffic"], bench)["driver"], bench)
        except cells.UnknownName as e:
            faults.append(f"{w['name']}: {e}")
        if w["chips"] not in (1, 4):
            faults.append(f"{w['name']}: chips {w['chips']}")
    four = [w["name"] for w in spec["workloads"] if w["chips"] == 4]
    if len(four) > max(1, len(spec["workloads"]) // 2):
        faults.append(f"{len(four)} of {len(spec['workloads'])} cells "
                      f"take 4 chips: {four}")
    cell_names = {w["name"] for w in spec["workloads"]}
    for m in metrics:
        for w in set(m.get("workloads", ())) - cell_names:
            faults.append(f"{m['name']}: names no cell {w!r}")
    for m in spec["end_to_end"]:
        if not 0.01 <= m["bound"] <= 0.25:
            faults.append(f"{m['name']}: bound {m['bound']}")
    e2e = {m["name"] for m in spec["end_to_end"]}
    for m in spec["per_layer"]:
        if m["moves"] not in e2e:
            faults.append(f"{m['name']}: moves {m['moves']!r}, no "
                          f"end-to-end metric")
    for c in spec["configs"]:
        if cells.config(c["name"], bench)["reduced"] != c["reduced"]:
            faults.append(f"{c['name']}: reduced differs from its file")
    return faults


def test_contract_shape_of_benchmark_json():
    assert contract_faults(SPEC) == []


def with_added_cells(tmp_path, chips):
    """A copy of ``bench/`` and ``BENCHMARK.json`` with one more cell per
    entry of ``chips``, each on its own traffic file."""
    bench = tmp_path / "bench"
    shutil.copytree(cells.BENCH, bench,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    spec = json.loads(json.dumps(SPEC))
    for i, n in enumerate(chips):
        traffic = {"driver": "served", "why": "dummy", "arrival": "poisson",
                   "rate_hz": 1.5, "mixes": ["Lang"], "bw_gb": [16]}
        (bench / "traffic" / f"added-{i}.json").write_text(
            json.dumps(traffic))
        spec["workloads"].append({"name": f"added.{i}",
                                  "config": SPEC["configs"][0]["name"],
                                  "traffic": f"added-{i}", "chips": n,
                                  "why": "dummy"})
    return spec, str(bench)


def test_a_four_chip_and_a_one_chip_cell_are_added_without_edits(tmp_path):
    spec, bench = with_added_cells(tmp_path, [4, 1])
    assert contract_faults(spec, bench) == []
    for w in spec["workloads"]:
        assert cells.cell(w["name"], spec, bench).chips == w["chips"]


def _second_four_chip_cell(spec):
    added = [w for w in spec["workloads"] if w["name"].startswith("added.")]
    spec["workloads"] = added + [dict(spec["workloads"][0], chips=4)]


def _unknown_cell_in_a_metric(spec):
    spec["end_to_end"][1]["workloads"] = ["no.such.cell"]


def _unknown_config(spec):
    spec["workloads"][0]["config"] = "no-such-config"


def _missing_traffic_file(spec):
    spec["workloads"][0]["traffic"] = "no-such-traffic"


def _two_chips(spec):
    spec["workloads"][0]["chips"] = 2


def _repeated_cell(spec):
    spec["workloads"].append(dict(spec["workloads"][0]))


def _bound_too_wide(spec):
    spec["end_to_end"][0]["bound"] = 0.3


def _bound_too_tight(spec):
    spec["end_to_end"][0]["bound"] = 0.005


@pytest.mark.parametrize("breach,fault", [
    (_second_four_chip_cell, "take 4 chips"),
    (_unknown_cell_in_a_metric, "names no cell"),
    (_unknown_config, "not listed"),
    (_missing_traffic_file, "no traffic file"),
    (_two_chips, "chips 2"),
    (_repeated_cell, "workloads: a name repeats"),
    (_bound_too_wide, "bound 0.3"),
    (_bound_too_tight, "bound 0.005"),
])
def test_a_copy_that_breaks_the_contract_fails_it(tmp_path, breach, fault):
    spec, bench = with_added_cells(tmp_path, [4, 1])
    breach(spec)
    assert any(fault in f for f in contract_faults(spec, bench)), \
        contract_faults(spec, bench)
