"""The benchmark's command: without a TPU it exits non-zero and prints no
result; in a directory with only ``BENCHMARK.json`` and the benchmark's
own files it fails too; and the CPU rehearsal of every cell drives the
whole run (set-up, window, comparison) and prints no device metric."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from perfbench import spec

B = spec.load()
CELLS = [w["name"] for w in B["workloads"]]


def _run(root, *args, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, os.path.join(root, "perfbench", "run.py"), *args],
        capture_output=True, text=True, env=env, timeout=600, cwd=root)


def _results(stdout):
    return [ln for ln in stdout.splitlines() if ln.startswith("{")]


def test_no_tpu_exits_nonzero_without_a_result():
    p = _run(str(spec.ROOT), "--workload", CELLS[0], "--seed", "2147483659",
             "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert _results(p.stdout) == []
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    for p in B["paths"]:
        shutil.copytree(spec.ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = {"PYTHONPATH": str(tmp_path)}
    p = _run(str(tmp_path), "--workload", CELLS[0], "--seed", "3",
             "--seconds", "1", "--trace", "0", "--rehearse", env_extra=env)
    assert p.returncode != 0
    assert _results(p.stdout) == []


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_drives_the_whole_run(cell):
    p = _run(str(spec.ROOT), "--workload", cell, "--seed", "2147483659",
             "--seconds", "2", "--trace", "0", "--rehearse")
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(_results(p.stdout)[-1])
    assert res["rehearsal"] is True and res["correct"] is True, res
    assert res["attempted"] > 0 and res["failed"] == 0
    assert "metrics" not in res and list(res)[-1] == "checks"
    assert p.stderr.rstrip().splitlines()[-1].startswith("check ")
