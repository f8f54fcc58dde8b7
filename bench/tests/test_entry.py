"""The measurement path refuses to run without what it measures."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT

ARGS = ["--workload", "websearch.call", "--seed", "2147483659",
        "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def _no_result(out: str) -> bool:
    for line in out.splitlines():
        try:
            if "correct" in json.loads(line):
                return False
        except (ValueError, TypeError):
            continue
    return True


def test_exits_nonzero_without_tpu():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert _no_result(p.stdout)


def test_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert _no_result(p.stdout)


def test_unknown_cell_is_refused():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "nope", "--seed", "1", "--seconds", "1"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0 and _no_result(p.stdout)


@pytest.mark.parametrize("flag", ["--workload", "--seed", "--seconds"])
def test_required_arguments(flag):
    args = list(ARGS)
    i = args.index(flag)
    del args[i:i + 2]
    p = subprocess.run([sys.executable, "bench/run.py", *args], cwd=ROOT,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and _no_result(p.stdout)
