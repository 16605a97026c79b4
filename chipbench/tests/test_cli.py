"""The command refuses to run without a TPU, and without the program."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
ARGS = ["--workload", "qwen2-0.5b.decode", "--seed", str(2**33 + 1),
        "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "chipbench/run.py", *ARGS],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def _no_result(p):
    for line in p.stdout.splitlines():
        try:
            assert not isinstance(json.loads(line), dict)
        except json.JSONDecodeError:
            pass


def test_refuses_cpu():
    p = _run(ROOT)
    assert p.returncode == 2, p.stderr[-2000:]
    assert "TPU" in p.stderr
    _no_result(p)


def test_fails_without_the_program(tmp_path):
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    p = _run(tmp_path)
    assert p.returncode != 0
    _no_result(p)
