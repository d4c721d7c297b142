"""The benchmark prints no result without the chip it needs, and none from
a directory that holds only the benchmark's own files."""
import os
import shutil
import subprocess
import sys
from types import SimpleNamespace

import pytest

from bench_tiny import REPO

from bench.run import NoChip, check_devices

ARGS = ["-m", "bench.run", "--workload", "qwen3-4b.chat-steady", "--seed", "5",
        "--seconds", "10", "--trace", "0"]


def _run(cwd, env_extra):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(env_extra)
    return subprocess.run([sys.executable, *ARGS], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    r = _run(REPO, {"JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_benchmark_files_alone_no_result(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert r.stdout.strip() == ""


def _dev(platform="tpu", kind="TPU v5 lite"):
    return SimpleNamespace(platform=platform, device_kind=kind)


def test_device_checks():
    rec, peaks = check_devices([_dev()], 1)
    assert rec == {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    assert peaks["bf16_flops"] == 197e12 and peaks["hbm_bytes_s"] == 819e9
    with pytest.raises(NoChip, match="not in bench/peaks.json"):
        check_devices([_dev(kind="TPU v9 imaginary")], 1)
    with pytest.raises(NoChip, match="needs 4 chips"):
        check_devices([_dev()], 4)
    with pytest.raises(NoChip, match="no TPU"):
        check_devices([_dev(platform="cpu", kind="cpu")], 1)
