"""``bench/run.py`` refuses to run without an accelerator."""
import os
import shutil
import subprocess
import sys

from bench.spec import BENCH, CHECKOUT


def _run(cwd):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "nemo-lora-train",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_no_accelerator_no_result():
    p = _run(CHECKOUT)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "accelerator" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(CHECKOUT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout == ""
