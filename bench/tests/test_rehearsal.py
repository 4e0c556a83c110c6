"""``bench/run.py`` end to end on the CPU, as a separate process: kernels in
interpret mode, on rehearsal fleets that exist only as new configuration
files and new cells beside the real ones."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


def bench(benchmark_file, *args, cwd=ROOT, interpret=True):
    cmd = [sys.executable, str(Path(cwd) / "bench/run.py"), *args,
           "--benchmark", str(benchmark_file)]
    if interpret:
        cmd.append("--device-interpret")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(cmd, capture_output=True, text=True, env=env,
                          cwd=cwd, timeout=600)


def last_json(proc):
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("workload", ["tiny-pelec.analyze", "tiny-amg.analyze",
                                      "tiny-pelec-routes.analyze"])
def test_end_to_end_metrics(benchmark_file, workload):
    proc = bench(benchmark_file, "--workload", workload, "--seed",
                 str(2**33 + 9), "--seconds", "2", "--trace", "0")
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = last_json(proc)
    assert list(out) == KEYS
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] % 6 == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"setup_s", "profiles_per_s", "db_MB"}
    assert out["device"]["platform"] == "cpu"
    assert set(out["checks"]) == set(json.loads(
        (ROOT / "bench/tests/configs/tiny-pelec.json").read_text())["limits"])
    tail = proc.stderr.strip().splitlines()[-len(out["checks"]):]
    assert all(line.startswith("check ") for line in tail)


def test_traced_run_reports_per_layer_metrics(benchmark_file):
    proc = bench(benchmark_file, "--workload", "tiny-pelec.analyze", "--seed",
                 "12", "--seconds", "2", "--trace", "1")
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = last_json(proc)
    # the CPU backend has no device plane: every per-layer metric of the
    # cell but the device trace's, and no breakdown
    assert list(out) == KEYS
    spec = json.loads(Path(benchmark_file).read_text())
    assert set(out["metrics"]) == {
        m["name"] for m in spec["per_layer"]
        if "tiny-pelec.analyze" in m["workloads"]
        and m["source"] != "device_trace"}
    assert "busy_s" not in out["device"]


def test_refuses_without_a_tpu(benchmark_file):
    proc = bench(benchmark_file, "--workload", "tiny-pelec.analyze", "--seed",
                 "1", "--seconds", "1", interpret=False)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr and "cpu" in proc.stderr
    assert not proc.stdout.strip()


def test_refuses_without_the_program(benchmark_file, tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files."""
    checkout = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", checkout / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", checkout / "BENCHMARK.json")
    proc = bench(benchmark_file, "--workload", "tiny-pelec.analyze", "--seed",
                 "1", "--seconds", "1", cwd=checkout)
    assert proc.returncode != 0
    assert not any(line.startswith("{\"correct\"")
                   for line in proc.stdout.splitlines())
