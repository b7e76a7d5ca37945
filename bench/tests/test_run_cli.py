"""bench/run.py refuses to run anywhere but on a TPU checkout."""
import os
import shutil
import subprocess
import sys

from conftest import BENCH, ROOT

ARGS = ["--workload", "paper4_edap_campaign", "--seed", "3000000001",
        "--seconds", "1", "--trace", "0"]


def _run(cwd, env_extra):
    env = dict(os.environ, **env_extra)
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_cpu_platform_exits_nonzero_and_names_it():
    p = _run(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert "platform 'cpu'" in p.stderr
    assert '"correct"' not in p.stdout


def test_lone_benchmark_directory_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    p = _run(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
