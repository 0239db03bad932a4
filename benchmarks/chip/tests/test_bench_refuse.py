"""The command refuses, printing no result: with no TPU (the CPU here),
and in a directory that holds only BENCHMARK.json and the benchmark's
files."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
ARGS = ["--workload", "qwen3-4b.search-rollout", "--seed", "2147483700",
        "--seconds", "1", "--trace", "0"]


def _run(cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cmd = [sys.executable] + bench["command"][1:] + ARGS
    return subprocess.run(cmd, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=120)


def _no_result(out: str) -> bool:
    for line in out.splitlines():
        try:
            if isinstance(json.loads(line), dict):
                return False
        except ValueError:
            continue
    return True


def test_refuses_without_a_tpu():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "no accelerator" in p.stderr
    assert _no_result(p.stdout)


def test_refuses_with_only_the_benchmark_files(tmp_path):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for d in bench["paths"]:
        shutil.copytree(ROOT / d, tmp_path / d,
                        ignore=shutil.ignore_patterns("out", "__pycache__"))
    env_path = os.environ.get("PYTHONPATH", "")
    try:
        os.environ["PYTHONPATH"] = ""
        p = _run(tmp_path)
    finally:
        os.environ["PYTHONPATH"] = env_path
    assert p.returncode != 0
    assert _no_result(p.stdout)
