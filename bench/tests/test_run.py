"""The entry refuses to run without a TPU, and without the program."""
import json
import os
import shutil
import subprocess
import sys

from conftest import BENCH, REPO

ARGS = ["--workload", "glm4-9b.decode", "--seed", "2147483659", "--seconds", "1",
        "--trace", "0"]


def _run(cwd, env_extra):
    env = dict(os.environ, **env_extra)
    return subprocess.run([sys.executable, "bench/run.py"] + ARGS, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def _no_result(p):
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    for ln in lines:
        try:
            json.loads(ln)
        except ValueError:
            continue
        raise AssertionError(f"printed a result line: {ln}")


def test_refuses_without_a_tpu():
    p = _run(REPO, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert "not a TPU" in p.stderr
    assert "device: platform=cpu" in p.stdout
    _no_result(p)


def test_refuses_without_the_program(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path), {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert "the program is not in" in p.stderr
    _no_result(p)
