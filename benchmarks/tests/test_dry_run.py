"""A ``--dry-run`` of every cell on the CPU ends in a line with the contract's
keys and no metric. About 20 s a cell."""

import json
import os
import subprocess
import sys

import pytest

from ddbench import spec

BENCH = spec.load_benchmark()


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_dry_run_prints_the_result_line(name, trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(spec.BENCH_DIR, "run.py"),
         "--workload", name, "--seed", str(2**31 + 5), "--seconds", "2",
         "--trace", str(trace), "--dry-run"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = out.stdout.strip().splitlines()
    assert lines[0] == "DRY RUN (cpu)"
    result = json.loads(lines[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} \
        <= set(result)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert result["metrics"] == {} and result["dry_run"] is True
    assert result["device"]["platform"] == "cpu"


def test_without_a_tpu_there_is_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(spec.BENCH_DIR, "run.py"),
         "--workload", BENCH["workloads"][0]["name"], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout
