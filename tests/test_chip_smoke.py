"""``chip_smoke.py`` off the chip: it refuses to run without a TPU, and its
phase functions pass at a tiny size (G=8, budget 200) on the CPU, with the
Pallas kernel run in the interpreter because the test asks for it."""
import importlib.util
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
G, BUDGET = 8, 200


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


cs = _load_chip_smoke()


@pytest.mark.parametrize("argv", [[], ["--four-chips"]])
def test_main_exits_nonzero_without_tpu(argv, capsys):
    assert os.environ.get("JAX_PLATFORMS") == "cpu"
    assert cs.main(argv) != 0
    out = capsys.readouterr()
    assert "TPU" in out.err
    assert '"ok"' not in out.out


@pytest.fixture(scope="module")
def trace():
    return cs.smoke_trace(group_size=G, num=8)


@pytest.fixture(scope="module")
def fits():
    return cs.grid_fits(group_size=G)


def test_smoke_trace_is_the_paper_mix(trace):
    assert len(trace) == 8 and {r.group_size for r in trace} == {G}
    assert {r.setting for r in trace} <= set(cs.SETTINGS)
    assert {r.mix for r in trace} <= set(cs.MIXES)
    full = cs.smoke_trace()
    assert len(full) == cs.NUM_REQUESTS
    assert {r.group_size for r in full} == {cs.GROUP_SIZE}


def test_phase_served_tiny(trace):
    info = cs.phase_served(trace, budget=BUDGET)
    assert info["requests"] == len(trace)
    assert info["memo_exact_hits"] == len(trace)
    assert info["keys_checked"] == len({r.setting for r in trace})
    assert all(e <= cs.RTOL for e in info["oracle_rel_err"].values())


def test_phase_offline_tiny(fits):
    info = cs.phase_offline(fits, budget=BUDGET)
    assert info["rows"] == len(cs.MIXES) * len(cs.SEEDS)


def test_phase_kernel_tiny_interpreted(fits):
    info = cs.phase_kernel(fits, budget=BUDGET, interpret=True)
    assert info["rows"] == len(cs.MIXES) * len(cs.SEEDS)
    assert info["max_rel_vs_oracle"] <= cs.RTOL


def test_phase_fleet_tiny(trace):
    """Two CPU workers with one device each stand in for the four chips."""
    fleet = cs.phase_fleet(trace[:4], budget=BUDGET, num_workers=2,
                           devices_per_worker=1)
    info = cs.check_fleet(fleet, trace[:4], budget=BUDGET, platform="cpu")
    assert info["requests"] == 4


def test_phase_sharded_tiny_on_four_fake_devices():
    code = textwrap.dedent(f"""
        import importlib.util, sys
        import jax
        assert len(jax.devices()) == 4, jax.devices()
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", {os.path.join(REPO, "chip_smoke.py")!r})
        cs = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(cs)
        sys.path.insert(0, cs.SRC)
        info = cs.phase_sharded(cs.smoke_trace(group_size={G}, num=8),
                                cs.grid_fits(group_size={G}),
                                budget={BUDGET}, devices=4)
        assert info["grid_rows"] == 8, info
        print("SHARDED-OK")
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SHARDED-OK" in out.stdout
