"""Without a TPU the benchmark measures nothing: it exits non-zero and
prints no result, and a device kind the peaks table lacks is an error."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

import rehearsal  # noqa: F401  (puts the harness on sys.path)
from harness import bench

ROOT = pathlib.Path(__file__).resolve().parents[2]


def _run(cwd, workload="serve.small_uniform"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", workload,
         "--seed", "2147483659", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


@pytest.mark.parametrize("workload", ["hss_sort.uniform_p4",
                                      "serve.small_uniform"])
def test_no_tpu_exits_nonzero_without_a_result(workload):
    proc = _run(ROOT, workload)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "perfbench", tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert not proc.stdout.strip()


def test_unknown_device_kind_is_an_error():
    assert bench.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        bench.load_peaks("cpu")


def test_peaks_name_their_source():
    table = json.loads((ROOT / "perfbench" / "peaks.json").read_text())
    assert "cloud.google.com" in table["source"]
