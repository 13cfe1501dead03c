"""chip_smoke.py rehearsed on the CPU (on-chip-measurement guide, section 2,
rehearsals 1 and 2): the same control flow at ``small_test`` width, reached
through the test-only ``size`` argument of its ``main()``.

Each rehearsal runs in a process of its own, as the smoke does: its driver
must be able to prove that it never imported jax, and it starts and stops a
cluster of its own.
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rehearse(argv, tmp_path, devices):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    proc = subprocess.run(
        [sys.executable, "-c",
         "import sys, chip_smoke; "
         f"sys.exit(chip_smoke.main({argv!r}, size='small'))"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    checks = {}
    for line in proc.stdout.splitlines():
        if line.startswith("chip_smoke: check "):
            name, _, verdict = line[len("chip_smoke: check "):].partition(": ")
            checks[name] = verdict == "ok"
    return proc, checks


def test_train_phase_runs_on_cpu_and_is_refused_for_the_platform(tmp_path):
    proc, checks = _rehearse([], tmp_path, devices=1)
    assert proc.returncode != 0, proc.stdout
    assert '"ok": true' not in proc.stdout
    assert "platform_is_tpu" in proc.stderr  # named among the failures
    assert checks.pop("platform_is_tpu") is False
    # the checks only a chip can pass
    assert checks.pop("auto_step_holds_tpu_custom_call") is False
    assert checks.pop("only_the_trainer_holds_the_device_library") is False
    # every phase ran up to that verdict: both attention settings trained,
    # the bystander came back from a CPU worker, the driver stayed off jax
    assert checks and all(checks.values()), (checks, proc.stdout)
    assert {"xla_losses_finite_and_falling",
            "auto_losses_finite_and_falling", "first_losses_agree",
            "xla_step_holds_no_custom_call",
            "bystander_did_not_hang", "driver_never_imported_jax"} <= set(checks)
    assert "bystander: returned 4.0" in proc.stdout
    assert "computed on cpu" in proc.stdout
    assert "tokens/s not measured (no chip)" in proc.stdout


def test_four_chip_comparison_passes_on_virtual_devices(tmp_path):
    proc, checks = _rehearse(["--chips", "4"], tmp_path, devices=4)
    assert proc.returncode != 0, proc.stdout
    assert '"ok": true' not in proc.stdout
    assert checks.pop("platform_is_tpu") is False
    assert checks and all(checks.values()), (checks, proc.stdout)
    assert {"xla_sharded_losses_match_one_device",
            "auto_sharded_losses_match_one_device",
            "every_device_holds_a_batch_shard",
            "large_batch_losses_finite_and_falling",
            "device_count"} <= set(checks)


def test_only_a_tpu_lease_may_see_the_chip(monkeypatch):
    """One process per chip, enforced by the raylet: on a node that
    advertises TPU, a worker whose lease holds none starts pinned to the
    CPU and pools apart from the workers that may open the device library
    — neither kind is ever handed the other's work."""
    import ray_tpu
    from ray_tpu._private.common import holds_tpu

    assert holds_tpu({"CPU": 1, "TPU": 1})
    assert holds_tpu({"TPU_group_0_abc": 4.0})  # a placement group's bundle
    assert not holds_tpu({"CPU": 1, "TPU": 0}) and not holds_tpu(None)

    # a chip machine's environment pins nothing; the cluster inherits it
    monkeypatch.setenv("JAX_PLATFORMS", "")
    ray_tpu.init(num_cpus=2, num_tpus=1)
    try:
        @ray_tpu.remote
        def where():
            return os.getpid(), os.environ.get("JAX_PLATFORMS")

        on_tpu = where.options(resources={"TPU": 1})
        plain = [ray_tpu.get(where.remote(), timeout=60) for _ in range(4)]
        leased = [ray_tpu.get(on_tpu.remote(), timeout=60) for _ in range(4)]
        plain += [ray_tpu.get(where.remote(), timeout=60) for _ in range(4)]
        assert {platforms for _, platforms in plain} == {"cpu"}
        assert {platforms for _, platforms in leased} == {""}
        assert not {pid for pid, _ in plain} & {pid for pid, _ in leased}
    finally:
        ray_tpu.shutdown()
