"""The command's contract where no chip is: it refuses, printing no
result."""
import os
import shutil
import subprocess
import sys

from bench import harness
from bench.run import main


def test_no_tpu_exits_non_zero_without_a_result(capsys):
    cell = harness.benchmark()["workloads"][0]["name"]
    rc = main(["--workload", cell, "--seed", str(2**33), "--seconds", "1",
               "--trace", "0"])
    out, err = capsys.readouterr()
    assert rc != 0
    assert out == ""
    assert "TPU" in err


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    cell = harness.benchmark()["workloads"][0]["name"]
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", cell, "--seed", "1",
         "--seconds", "1", "--trace", "0"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
