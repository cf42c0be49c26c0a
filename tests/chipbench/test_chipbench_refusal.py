"""No number of the benchmark comes from a CPU: without a TPU the command
exits non-zero and prints no result line, and so it does in a directory
that holds only the benchmark's own files."""
import os
import shutil
import subprocess
import sys
from unittest import mock

import pytest

from chipbench import bench, run


def _run(cwd, timeout=120):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", "k50_pair",
         "--seed", "4294967311", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=timeout)


def _no_result(proc):
    assert proc.returncode != 0
    last = (proc.stdout.strip().splitlines() or [""])[-1]
    assert not last.startswith("{")


def test_refuses_without_a_tpu():
    proc = _run(bench.ROOT)
    _no_result(proc)
    assert proc.returncode == 2
    assert "no TPU" in proc.stderr


def test_refuses_in_a_bare_checkout(tmp_path):
    """Only BENCHMARK.json and the files under ``paths``: the program is
    missing, and the run fails before any result."""
    spec = bench.load_benchmark()
    shutil.copy(bench.ROOT / "BENCHMARK.json", tmp_path)
    for path in spec["paths"]:
        shutil.copytree(bench.ROOT / path, tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    _no_result(_run(tmp_path))


def test_attach_refuses_cpu_and_too_few_chips():
    import jax
    if jax.devices()[0].platform == "tpu":
        pytest.skip("a TPU is attached")
    with pytest.raises(run.NoChip, match="no TPU"):
        run.attach(1)


def test_cache_dir_is_inside_the_checkout():
    with mock.patch.dict(os.environ,
                         {"JAX_COMPILATION_CACHE_DIR": "/elsewhere"}):
        cache = run.set_cache_env()
        assert cache == str(bench.ROOT / ".jax_cache")
        assert os.environ["JAX_COMPILATION_CACHE_DIR"] == cache
