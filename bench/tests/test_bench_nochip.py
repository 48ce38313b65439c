"""``bench/run.py`` refuses to run without a TPU, and outside a checkout of
the simulator, printing no result line either time."""

import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
ARGS = ["--workload", "q19_sugar", "--seed", "3", "--seconds", "1",
        "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def _no_result(p):
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


def test_no_tpu_exits_nonzero_without_a_result():
    p = _run(ROOT)
    _no_result(p)
    assert "no TPU" in p.stderr


def test_lone_benchmark_exits_nonzero_without_a_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    p = _run(tmp_path)
    _no_result(p)
    assert "not in this checkout" in p.stderr
