"""``chip_smoke.py``'s phases at a tiny size on the CPU backend.

The script refuses to run anywhere but on a TPU, so these drive its phase
functions directly (Pallas kernels interpreted) with the same checks it
applies on the chip, and show that the checks catch a wrong answer.
"""
import importlib.util
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config

REPO = Path(__file__).resolve().parents[1]
SPACE, TILE = (8, 16, 32), (4, 8, 16)


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_main_refuses_to_run_off_a_tpu(chip_smoke, capsys):
    assert chip_smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert out == ""  # no result line
    assert "needs a TPU" in err and "'cpu'" in err


def test_stencil_phase_matches_reference(chip_smoke):
    facts = chip_smoke.stencil_phase(SPACE, TILE, seed=3)
    assert facts["tiles"] == 8 and facts["waves"] == 4
    assert facts["max_error"] <= facts["tolerance"]
    assert not facts["kernel_lowered"]  # interpreted on the CPU backend


def test_stencil_check_catches_a_wrong_facet(chip_smoke):
    from repro import cfa

    compiled = cfa.compile("jacobi2d5p", SPACE, layout=TILE,
                           target="tpu-v5e-hbm", backend="pallas")
    inputs = jnp.ones((1, *SPACE[1:]), jnp.float32)
    facets = compiled(inputs)
    assert chip_smoke._max_facet_error(facets, compiled, inputs) <= 1e-6
    facets[2] = facets[2].at[(0,) * facets[2].ndim].add(1.0)
    assert chip_smoke._max_facet_error(facets, compiled, inputs) >= 1.0


def test_serve_phase_agrees_with_teacher_forcing(chip_smoke):
    facts = chip_smoke.serve_phase(get_smoke_config("qwen3-0.6b"), seed=1,
                                   n_requests=5, prompt_lens=(8, 16),
                                   new_tokens=5, lanes=2)
    assert facts["answered"] == 5 and facts["tokens"] == 25


def test_serve_check_catches_wrong_tokens(chip_smoke, monkeypatch):
    class Garbling(chip_smoke.ContinuousBatcher):
        def _retire(self, lane):
            req = self.active[lane]
            req.out[:] = [0] * len(req.out)
            super()._retire(lane)

    monkeypatch.setattr(chip_smoke, "ContinuousBatcher", Garbling)
    with pytest.raises(AssertionError, match="below the top"):
        chip_smoke.serve_phase(get_smoke_config("qwen3-0.6b"), seed=1,
                               n_requests=2, prompt_lens=(8,),
                               new_tokens=5, lanes=2)


_SHARDED_SCRIPT = textwrap.dedent("""
    import importlib.util, os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    spec = importlib.util.spec_from_file_location("chip_smoke", sys.argv[1])
    cs = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cs)
    facts = cs.sharded_phase((8, 8, 8, 16), (4, 4, 4, 8), n_chips=4)
    assert facts["n_devices"] == 4
    assert sorted(d for ds in facts["placement"].values() for d in ds) == [0, 1, 2, 3]
    print("SHARDED_OK")
""")


def test_sharded_phase_on_four_host_devices_subprocess():
    """--chips 4's path on four forced host devices: one facet array per
    device, the result equal to the reference."""
    from conftest import multidevice_emulation_reason

    reason = multidevice_emulation_reason()
    if reason is not None:
        pytest.skip(f"multi-device emulation unavailable: {reason}")
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu")
    res = subprocess.run(
        [sys.executable, "-c", _SHARDED_SCRIPT, str(REPO / "chip_smoke.py")],
        capture_output=True, text=True, env=env, timeout=600)
    assert "SHARDED_OK" in res.stdout, (
        f"stdout={res.stdout}\nstderr={res.stderr[-3000:]}")
