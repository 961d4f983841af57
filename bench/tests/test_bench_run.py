"""The command exits non-zero and prints no result where it cannot
measure: no TPU, or a directory that holds only the benchmark."""
import os
import shutil
import subprocess
import sys

from bench import spec as SP

ARGS = ["--workload", "smollm360m.alpaca_poisson", "--seed", "2147483700",
        "--seconds", "1", "--trace", "0"]


def run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=120)


def test_no_tpu_exits_nonzero_without_a_result():
    p = run(SP.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_benchmark_alone_cannot_run(tmp_path):
    shutil.copy(SP.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(SP.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
