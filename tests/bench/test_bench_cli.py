"""``bench/run.py`` measures a TPU or nothing: off a TPU, or without the
program under test beside it, it exits non-zero and prints no result."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402

ARGS = ["--workload", "jacobi2d5p-medium", "--seed", "5", "--seconds", "1", "--trace", "0"]


def test_refuses_the_cpu(capsys):
    if jax.devices()[0].platform == "tpu":
        pytest.skip("this host has a TPU")
    run = harness.load_module(ROOT / "bench" / "run.py")
    assert run.main(ARGS) == 1
    out, err = capsys.readouterr()
    assert out == "" and "needs a TPU" in err
    assert harness.accelerator(1)[0] is None


def test_unknown_workload_is_an_error():
    with pytest.raises(KeyError, match="no workload named"):
        harness.cell_spec(harness.load_manifest(ROOT), "no-such-cell")


def test_benchmark_files_alone_print_no_result(tmp_path):
    """A directory holding only BENCHMARK.json and the files under
    ``paths`` has no program to measure."""
    manifest = harness.load_manifest(ROOT)
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in manifest["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, *manifest["command"][1:], *ARGS],
                         cwd=tmp_path, env=env, capture_output=True, text=True,
                         timeout=300)
    assert res.returncode != 0
    assert "{" not in res.stdout
