"""The harness refuses to run without a TPU, and where the program is
missing, and prints no result either way."""
import json
import os
import shutil
import subprocess
import sys

import chip_tiny

HARNESS = os.path.join(chip_tiny.CHIP, "harness.py")
ARGS = ["--workload", "mamba2_370m_train.cycle", "--seed", "2147483659",
        "--seconds", "1", "--trace", "0"]


def _run(harness, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, harness, *ARGS], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def _no_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if "correct" in json.loads(line):
                return False
        except (ValueError, TypeError):
            continue
    return True


def test_harness_exits_nonzero_without_a_tpu():
    p = _run(HARNESS, chip_tiny.ROOT)
    assert p.returncode != 0
    assert "TPU" in p.stderr
    assert _no_result(p.stdout)


def test_harness_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(os.path.join(chip_tiny.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(chip_tiny.CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path / "benchmarks" / "chip" / "harness.py"), tmp_path)
    assert p.returncode != 0
    assert _no_result(p.stdout)
