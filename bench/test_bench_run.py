"""``bench/run.py`` refuses to run without a TPU, and prints no result."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import run

ROOT = Path(__file__).resolve().parent.parent


def _bench(cwd, *extra):
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "ALLOW_MULTIPLE_LIBTPU_LOAD")}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "paper-mlp",
         "--seed", "3", "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=240)


def test_no_tpu_exits_without_result():
    p = _bench(ROOT)
    assert p.returncode == run.EXIT_NO_CHIP, p.stderr[-2000:]
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _bench(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("values,q,want", [
    ([1.0], 95, 1.0),
    ([1.0, 2.0, 3.0, 4.0, 5.0], 50, 3.0),
    ([0.0, 10.0], 95, 9.5),
])
def test_percentile(values, q, want):
    assert run.percentile(values, q) == pytest.approx(want)


def test_job_seeds_differ_and_repeat():
    big = 2 ** 31 + 17
    seeds = [run.job_seed(big, j) for j in range(4)]
    assert len(set(seeds)) == 4
    assert seeds == [run.job_seed(big, j) for j in range(4)]
    assert all(0 <= s < 2 ** 63 for s in seeds)
