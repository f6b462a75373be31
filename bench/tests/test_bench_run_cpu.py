"""`bench/run.py` refuses to run without a TPU, and without the program."""
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
ARGS = ["--workload", "ct-ieks.fleet-pow2", "--seed", "3000000001",
        "--seconds", "1", "--trace", "0"]


def _copy_bench_only(dst):
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    return dst


@pytest.mark.parametrize("where", ["checkout", "bench_only"])
def test_run_exits_nonzero_without_a_tpu(where, tmp_path):
    cwd = ROOT if where == "checkout" else _copy_bench_only(str(tmp_path))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("PYTHONPATH", None)
    proc = subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
