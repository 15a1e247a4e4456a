"""The seeded request generator."""
from collections import Counter

import pytest

from benchlib import cells, traffic

CFG = cells.config("magma-table3-g100")
POISSON = {"driver": "served", "arrival": "poisson", "rate_hz": 2.5,
           "mixes": ["Vision", "Lang", "Recom", "Mix"],
           "bw_gb": [1, 4, 16, 64]}


def test_same_seed_same_requests_and_large_seeds_work():
    a = traffic.requests(POISSON, CFG, 2 ** 33 + 5, 40)
    b = traffic.requests(POISSON, CFG, 2 ** 33 + 5, 40)
    c = traffic.requests(POISSON, CFG, 2 ** 33 + 6, 40)
    assert a == b and a != c
    # another seed: the same instants, another order of the work
    assert [r["due_s"] for r in a] == [r["due_s"] for r in c]
    assert [r["setting"] for r in a] != [r["setting"] for r in c]


def test_each_block_holds_every_value_once():
    reqs = traffic.requests(POISSON, CFG, 17, 40)
    for i in range(0, 96, 6):
        assert sorted(r["setting"] for r in reqs[i:i + 6]) == CFG["settings"]


@pytest.mark.parametrize("seed", [1, 99, 123456789012])
def test_every_seed_offers_the_same_work_in_the_window(seed):
    reqs = traffic.requests(POISSON, CFG, seed, 40)
    assert len(reqs) == 100
    assert all(0 <= r["due_s"] < 40 for r in reqs)
    assert [r["due_s"] for r in reqs] == sorted(r["due_s"] for r in reqs)
    settings = Counter(r["setting"] for r in reqs)
    assert sorted(settings) == CFG["settings"]
    assert set(settings.values()) <= {16, 17}
    assert Counter(r["mix"] for r in reqs) == Counter(
        {m: 25 for m in POISSON["mixes"]})
    assert {r["group_size"] for r in reqs} == {100}
    assert all(0 <= r["seed"] < 2 ** 31 - 1 for r in reqs)


@pytest.mark.parametrize("realisation", [1, 2])
def test_another_realisation_moves_the_instants_not_the_work(realisation):
    a = traffic.requests(POISSON, CFG, 4, 40)
    b = traffic.requests(POISSON, CFG, 4, 40, realisation)
    assert len(a) == len(b) == 100
    assert [r["due_s"] for r in a] != [r["due_s"] for r in b]
    strip = lambda rs: [{k: v for k, v in r.items() if k != "due_s"}  # noqa
                        for r in rs]
    assert strip(a) == strip(b)


def test_offline_grid_covers_every_pair():
    t = cells.traffic("offline-grid")
    g = traffic.grid(t, cells.config("magma-s4-g100"))
    assert len(g) == 16
    assert {(s["mix"], s["bw_gb"]) for s in g} == {
        (m, float(b)) for m in t["mixes"] for b in t["bw_gb"]}


def test_unknown_arrival_is_an_error():
    with pytest.raises(ValueError, match="arrival"):
        traffic.requests(dict(POISSON, arrival="weird"), CFG, 1, 10)
