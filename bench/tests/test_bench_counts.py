"""The fitness work count and the roofline arithmetic."""
import pytest

from benchlib import counts


def test_fitness_work_by_hand():
    # one individual, 2 jobs on 3 cores: 6 ops x 2 events x 3 cores;
    # genomes 2 x 8 B, tables 2 x 3 x 8 B, one makespan 4 B
    assert counts.fitness_work(1, 3, 2) == (36.0, 16.0 + 48.0 + 4.0)
    flops, nbytes = counts.fitness_work(100, 8, 100)
    assert flops == 480_000.0
    assert nbytes == 80_000.0 + 6_400.0 + 400.0


def test_roofline_share_names_its_bound():
    pk = counts.peaks("TPU v5 lite")
    share, bound = counts.roofline_share(0.0, pk["bytes_per_s"], 2.0,
                                         "TPU v5 lite")
    assert (share, bound) == (pytest.approx(50.0), "memory")
    share, bound = counts.roofline_share(pk["flops_per_s"], 0.0, 4.0,
                                         "TPU v5 lite")
    assert (share, bound) == (pytest.approx(25.0), "compute")


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        counts.peaks("cpu")
