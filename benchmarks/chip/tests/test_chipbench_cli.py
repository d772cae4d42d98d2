"""The chip benchmark's command refuses to measure where it cannot."""
import json
import os
import shutil
import subprocess
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(BENCH))
ARGS = ["--workload", "cityflow.steady", "--seed", "3", "--seconds", "1",
        "--trace", "0"]


def _run(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "JAX_COMPILATION_CACHE_DIR")}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmarks", "chip", "run.py"),
         *ARGS], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def _result_lines(out):
    lines = []
    for ln in out.splitlines():
        try:
            if isinstance(json.loads(ln), dict):
                lines.append(ln)
        except ValueError:
            pass
    return lines


def test_cpu_backend_exits_nonzero_without_a_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert not _result_lines(p.stdout)
    assert "needs a TPU" in p.stderr


def test_checkout_with_only_the_benchmark_fails(tmp_path):
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = _run(str(tmp_path))
    assert p.returncode != 0
    assert not _result_lines(p.stdout)
