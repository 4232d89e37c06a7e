"""run.py exits non-zero, naming the backend, off the chip; and prints no
result line."""

import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _run(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), *args],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)


def test_cpu_backend_is_refused_by_name():
    p = _run("--workload", "resnet50.steady", "--seed", "1", "--seconds",
             "1", "--trace", "0")
    assert p.returncode != 0
    assert "'cpu'" in p.stderr and "TPU" in p.stderr
    assert '"metrics"' not in p.stdout


def test_unknown_workload_is_refused():
    p = _run("--workload", "nope", "--seed", "1", "--seconds", "1",
             "--trace", "0")
    assert p.returncode != 0
    assert "not in BENCHMARK.json" in p.stderr
    assert '"metrics"' not in p.stdout
