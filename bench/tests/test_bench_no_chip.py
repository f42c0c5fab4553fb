"""Without a TPU, and in a directory that holds only the benchmark's own
files, the command exits non-zero and prints no result."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def run_cell(cwd: Path) -> subprocess.CompletedProcess:
    cell = json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"][0]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", cell["name"],
         "--seed", str(2**31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def no_result(out: str) -> bool:
    return not any(line.lstrip().startswith("{") for line in out.splitlines())


def test_cpu_is_refused(tmp_path):
    src = tmp_path / "checkout"
    shutil.copytree(BENCH, src / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    shutil.copytree(ROOT / "src", src / "src",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", src)
    p = run_cell(src)
    assert p.returncode != 0
    assert no_result(p.stdout)
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_are_refused(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    p = run_cell(tmp_path)
    assert p.returncode != 0
    assert no_result(p.stdout)
