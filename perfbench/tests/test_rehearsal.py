"""The whole command, rehearsed on the CPU at a toy configuration
(perfbench/tests/rehearsal.json: same keys as BENCHMARK.json, ``small_test``
sizes, never a benchmark run): control flow, the whole-period interval, the
read-back of every acknowledged save, the reference comparison and the
shape of the last line. And the real command, which must fail here: this
machine has no chip.

Each run is a process of its own, as the driver makes it: it starts and
stops a cluster, and must prove it never imported jax.
"""

import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
REHEARSAL = os.path.join("perfbench", "tests", "rehearsal.json")


def _run(workload, trace, tmp_path, devices=1, bench_file=REHEARSAL,
         seconds=2, seed=2**31 + 11):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"),
               TMPDIR=str(tmp_path), BENCH_RUN="ignored")
    argv = [sys.executable, os.path.join("perfbench", "run.py"),
            "--workload", workload, "--seed", str(seed),
            "--seconds", str(seconds), "--trace", str(trace)]
    if bench_file:
        argv += ["--bench-file", bench_file]
    proc = subprocess.run(argv, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=600)
    lines = proc.stdout.strip().splitlines()
    last = None
    if lines and lines[-1].startswith("{"):
        last = json.loads(lines[-1])
    return proc, last


def _checks(proc):
    return dict(re.findall(r"^perfbench: check (\w+): (ok|FAILED)$",
                           proc.stdout, re.M))


def _assert_line(last, metrics):
    assert set(last) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert last["rehearsal"] is True and last["device"]["platform"] == "cpu"
    assert last["correct"] is True and last["failed"] == 0
    assert set(last["metrics"]) == set(metrics)
    for m in last["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert last["device"]["memory_peak_bytes"] > 0


@pytest.mark.parametrize("workload,devices", [
    ("tiny.step", 1), ("tiny.step-fsdp4", 4)])
def test_step_cells_rehearse(workload, devices, tmp_path):
    proc, last = _run(workload, 0, tmp_path, devices)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    _assert_line(last, {"tokens_per_s_per_chip", "setup_s"})
    assert last["device"]["count"] == devices
    checks = _checks(proc)
    assert checks and set(checks.values()) == {"ok"}, checks
    assert "reference_within_tolerance" in checks
    steps = int(re.search(r"interval: (\d+) period", proc.stdout).group(1))
    assert last["attempted"] == steps > 10
    # the interval fits the window and nearly fills it
    interval = float(re.search(r"([\d.]+) s on the worker's clock",
                               proc.stdout).group(1))
    assert 1.5 < interval <= 2.0
    assert "first eight losses" in proc.stdout
    assert "set-up phases" in proc.stdout and "HBM plan" in proc.stdout


def test_job_cell_measures_whole_save_periods_and_reads_every_save_back(
        tmp_path):
    proc, last = _run("tiny.job", 0, tmp_path)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    _assert_line(last, {"job_tokens_per_s_per_chip", "setup_s"})
    m = re.search(r"interval: (\d+) period\(s\), (\d+) steps, (\d+) save",
                  proc.stdout)
    periods, steps, saves = (int(g) for g in m.groups())
    assert periods >= 1 and steps == 6 * periods and saves == periods
    assert last["attempted"] == steps + saves
    read_back = json.loads(re.search(r"read back: (\{.*\})", proc.stdout)
                           .group(1).replace("'", '"'))
    assert read_back == {"read_back": saves, "failed": 0, "problems": []}
    assert set(_checks(proc).values()) == {"ok"}
    # nothing of the run is left in its TMPDIR
    assert [p for p in os.listdir(tmp_path) if p.startswith("perfbench_")] == []


def test_traced_job_run_reports_the_per_layer_metrics_a_cpu_can(tmp_path):
    proc, last = _run("tiny.job", 1, tmp_path)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    # no device plane on a CPU: every device_trace reader finds nothing and
    # is left out; spans and host-clock metrics are there
    _assert_line(last, {"gang_start_s", "data_wait_pct", "ckpt_stall_ms",
                        "hbm_plan_gib.job"})
    assert "busy_s" not in last["device"]
    assert last["attempted"] == 6 + 1


def test_a_save_that_does_not_read_back_counts_as_failed(tmp_path):
    import numpy as np

    from perfbench import readback

    expect = {"trial_dir": str(tmp_path), "leaf_paths": ["params/w"],
              "saves": [{"index": 3, "sums": [readback.checksum(
                  np.ones(4, np.float32))]}]}
    out = readback.verify(expect)  # nothing on disk
    assert out["read_back"] == 1 and out["failed"] == 1


def test_the_real_command_fails_without_a_chip(tmp_path):
    proc, last = _run("gpt2-124m.step", 0, tmp_path, bench_file=None)
    assert proc.returncode != 0
    assert last is None and '"metrics"' not in proc.stdout
    assert "needs 1 tpu chip" in proc.stderr
