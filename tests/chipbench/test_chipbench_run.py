"""The command refuses to run without a TPU, and in a directory that holds
only the benchmark, with a non-zero exit and no result line."""

import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import spec  # noqa: E402


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    cmd = spec.benchmark()["command"] + ["--workload", "olmo1b-train-2k", "--seed",
                                         str(2**31 + 7), "--seconds", "1", "--trace", "0"]
    cmd[0] = sys.executable
    return subprocess.run(cmd, cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_a_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for d in spec.benchmark()["paths"]:
        shutil.copytree(ROOT / d, tmp_path / d, ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
