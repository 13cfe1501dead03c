"""chip_smoke.py — does the system still start on the chip?

Drives the training path once through the entry points a user calls:
``ray_tpu.init`` (real GCS + raylet + worker processes), then
``JaxTrainer(...).fit()`` with a loop that trains GPT-2-124M at full width
(768 x 12 layers x 12 heads, vocabulary 50257) at batch 16 x 1024 on random
tokens made from a seed, once with ``attention="xla"`` (XLA's own attention,
no kernel) and once with ``attention="auto"``, which at this shape on a TPU
is the Pallas flash kernel. While the trainer's worker holds the chip, a plain
task touches jax in another worker (the bystander). The worker is the only
process that may touch JAX; this driver reads what it reports from the
``Result`` and never imports jax itself.

    python chip_smoke.py            # one chip: train + bystander
    python chip_smoke.py --chips 4  # only the data=4 mesh path, against
                                    # the same steps on a one-device mesh

The last line of standard output is, on success only,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``
with the device as the worker's JAX reports it. Any failed check, a
platform other than ``tpu`` included, prints the failures and exits 1 with
no such line. Times and tokens/s on the earlier lines are a smoke run's,
not a benchmark's.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import statistics
import sys
import tempfile
import threading
import time

import ray_tpu
from ray_tpu._private.compile_cache import place_compile_cache

SEED = 0
WARMUP_STEPS, TIMED_STEPS = 3, 5
MESH_STEPS = 5
BYSTANDER_TIMEOUT_S = 60.0
# Loss differences the comparisons tolerate: bf16 keeps 8 bits of mantissa
# (relative 2^-8 = 0.4%), the losses here sit near ln(vocab) = 10.8.
LOSS_TOL = 0.05
WALL_BUDGET_S = 1100.0  # the contract gives 1200 s, compilation included

# "full" is the smoke; "small" is the CPU rehearsal of the same control flow
# (tests/test_chip_smoke_cpu.py), reachable only through main()'s argument.
SIZES = {
    "full": {"config": "gpt2_124m", "batch": 16, "seq": 1024,
             "mesh_batches": (8, 64)},
    "small": {"config": "small_test", "batch": 4, "seq": 128,
              "mesh_batches": (8, 16), "runs_without_a_chip": True},
}


def _say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


# ----------------------------------------------------------------------
# what runs in the workers
# ----------------------------------------------------------------------

@ray_tpu.remote
def _bystander():
    """A task that holds no TPU resource and touches jax all the same."""
    import jax

    total = float(jax.numpy.ones(4).sum())
    return {"sum": total, "platform": jax.devices()[0].platform,
            "pid": os.getpid(),
            "jax_platforms": os.environ.get("JAX_PLATFORMS")}


def _run_bystander() -> dict:
    t0 = time.monotonic()
    try:
        out = ray_tpu.get(_bystander.remote(), timeout=BYSTANDER_TIMEOUT_S)
        out["outcome"] = "returned"
    except ray_tpu.GetTimeoutError:
        out = {"outcome": "hang"}
    except Exception as e:  # reported to the driver, which decides
        out = {"outcome": "raised", "error": f"{type(e).__name__}: {e}"}
    out["seconds"] = time.monotonic() - t0
    return out


def _device_library_holders() -> list:
    """Processes of this host that have the TPU library mapped."""
    holders = []
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{pid}/maps") as f:
                if "libtpu" not in f.read():
                    continue
            with open(f"/proc/{pid}/cmdline") as f:
                cmd = f.read().replace("\0", " ").strip()
        except OSError:  # gone, or not ours to read
            continue
        holders.append({"pid": int(pid), "cmd": cmd[-100:]})
    return holders


def _device_report(jax) -> dict:
    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def _no_chip(train, device: dict, size: dict) -> bool:
    """Without a chip the full-width phases would outlast the time limit
    on a CPU only to be refused for the platform: report the device and
    stop. The rehearsal size goes on, to exercise the control flow."""
    if device["platform"] == "tpu" or size.get("runs_without_a_chip"):
        return False
    train.report({"summary": {"device": device, "worker_pid": os.getpid()}})
    return True


def _memory(device) -> dict:
    stats = device.memory_stats() or {}  # the CPU backend reports none
    return {k: stats.get(k) for k in ("bytes_in_use", "peak_bytes_in_use")}


def _fenced_steps(jax, step, params, opt_state, batch, n):
    """n steps, each fenced -> (params, opt_state, losses, seconds)."""
    losses, seconds = [], []
    for _ in range(n):
        t0 = time.perf_counter()
        params, opt_state, loss = step(params, opt_state, batch)
        jax.block_until_ready((params, opt_state, loss))
        seconds.append(time.perf_counter() - t0)
        losses.append(float(loss))
    return params, opt_state, losses, seconds


def _model_config(gpt2, size: dict, attention: str):
    return getattr(gpt2.GPT2Config, size["config"])(
        loss_chunks=8, attention=attention)


def train_loop(config):
    """The one-chip phase: what a user's train loop does, twice."""
    import jax

    from ray_tpu import train
    from ray_tpu.models import gpt2

    size = config["size"]
    device = _device_report(jax)
    if _no_chip(train, device, size):
        return
    runs, bystander, holders = {}, None, None
    for attention in ("xla", "auto"):
        cfg = _model_config(gpt2, size, attention)
        model, params, tx, opt_state = gpt2.make_train_state(
            cfg, jax.random.PRNGKey(SEED))
        batch = gpt2.synthetic_batch(jax.random.PRNGKey(SEED + 1),
                                     size["batch"], size["seq"],
                                     cfg.vocab_size)
        step = gpt2.build_train_step(model, tx, donate=True)
        # Ahead-of-time so that compile time, the lowered program and the
        # compiler's memory plan are read off the very program that runs.
        lowered = step.lower(params, opt_state, batch)
        has_custom_call = "tpu_custom_call" in lowered.as_text()
        t0 = time.perf_counter()
        compiled = lowered.compile()
        compile_s = time.perf_counter() - t0
        plan = compiled.memory_analysis()
        params, opt_state, warm_losses, _ = _fenced_steps(
            jax, compiled, params, opt_state, batch, WARMUP_STEPS)
        if bystander is None:
            # the chip is ours and busy: now let another worker touch jax
            bystander = _run_bystander()
            holders = _device_library_holders()
        params, opt_state, losses, seconds = _fenced_steps(
            jax, compiled, params, opt_state, batch, TIMED_STEPS)
        for i, (loss, s) in enumerate(zip(losses, seconds)):
            train.report({"attention": attention, "step": WARMUP_STEPS + i,
                          "loss": loss, "step_s": s})
        runs[attention] = {
            "losses": warm_losses + losses, "step_s": seconds,
            "compile_s": compile_s, "tpu_custom_call": has_custom_call,
            "planned_bytes": plan.temp_size_in_bytes
            + plan.argument_size_in_bytes + plan.output_size_in_bytes
            - plan.alias_size_in_bytes,
            "memory": _memory(jax.devices()[0]),
            "tokens_per_step": size["batch"] * size["seq"],
        }
        # "xla" at batch 16 leaves little of the 16 GB: free before "auto"
        del model, params, tx, opt_state, batch, step, lowered, compiled
    train.report({"summary": {
        "device": device, "worker_pid": os.getpid(), "runs": runs,
        "bystander": bystander, "device_library_holders": holders,
    }})


def mesh_loop(config):
    """The four-chip phase: the same step over data=4, held against a
    one-device mesh in the same process, then at 16 sequences per chip."""
    import jax

    from ray_tpu import parallel, train
    from ray_tpu.models import gpt2

    size = config["size"]
    device = _device_report(jax)
    if _no_chip(train, device, size):
        return
    small_batch, large_batch = size["mesh_batches"]

    def run(attention, n_devices, batch_size):
        mesh = parallel.create_mesh({"data": n_devices})
        cfg = _model_config(gpt2, size, attention)
        model, params, tx, opt_state = gpt2.make_train_state(
            cfg, jax.random.PRNGKey(SEED))
        params, opt_state = gpt2.shard_train_state(params, opt_state, mesh)
        batch = gpt2.shard_batch(
            gpt2.synthetic_batch(jax.random.PRNGKey(SEED + 1), batch_size,
                                 size["seq"], cfg.vocab_size), mesh)
        # code that has only met one chip may put everything on the first
        shards = [{"device": str(s.device), "rows": s.data.shape[0],
                   **_memory(s.device)}
                  for s in batch["input_ids"].addressable_shards]
        step = gpt2.build_train_step(model, tx, donate=True)
        t0 = time.perf_counter()
        params, opt_state, first, _ = _fenced_steps(
            jax, step, params, opt_state, batch, 1)
        first_step_s = time.perf_counter() - t0
        params, opt_state, losses, seconds = _fenced_steps(
            jax, step, params, opt_state, batch, MESH_STEPS - 1)
        out = {"attention": attention, "devices": n_devices,
               "batch": batch_size, "losses": first + losses,
               "first_step_s": first_step_s, "step_s": seconds,
               "shards": shards,
               "tokens_per_step": batch_size * size["seq"]}
        train.report({k: out[k] for k in ("attention", "devices", "batch",
                                          "losses")})
        return out  # locals die here: one state is freed before the next

    runs = []
    for attention in ("xla", "auto"):
        runs.append(run(attention, 4, small_batch))
        runs.append(run(attention, 1, small_batch))
    for attention in ("xla", "auto"):
        runs.append(run(attention, 4, large_batch))
    train.report({"summary": {"device": device, "worker_pid": os.getpid(),
                              "runs": runs}})


# ----------------------------------------------------------------------
# the driver: never imports jax
# ----------------------------------------------------------------------

def _finite_and_falling(losses) -> bool:
    return all(math.isfinite(x) for x in losses) and losses[-1] < losses[0]


def _gib(n) -> str:
    return "not reported" if n is None else f"{n / 2**30:.2f} GiB"


def _rate(run: dict, step_s: float, device: dict) -> str:
    """Tokens/s of a smoke run — of the chip only: a CPU's is no device
    number under any label."""
    if device["platform"] != "tpu":
        return "tokens/s not measured (no chip)"
    return (f"{run['tokens_per_step'] / step_s:,.0f} tokens/s (smoke, not "
            f"a metric)")


def _check_train(summary: dict, checks: dict) -> dict:
    """Print and judge the one-chip phases -> compile seconds by run."""
    runs = summary["runs"]
    for attention, r in runs.items():
        step_s = statistics.median(r["step_s"])
        _say(f"train[{attention}]: losses "
             f"{[round(x, 4) for x in r['losses']]}")
        _say(f"train[{attention}]: compile {r['compile_s']:.2f} s, step "
             f"median {step_s * 1e3:.1f} ms over {len(r['step_s'])} steps, "
             f"{_rate(r, step_s, summary['device'])}; HBM the compiler "
             f"plans for the step {_gib(r['planned_bytes'])}, allocator "
             f"peak of the process so far "
             f"{_gib(r['memory']['peak_bytes_in_use'])}; "
             f"tpu_custom_call in the lowered step: {r['tpu_custom_call']}")
        checks[f"{attention}_losses_finite_and_falling"] = \
            _finite_and_falling(r["losses"])
    checks["first_losses_agree"] = abs(
        runs["xla"]["losses"][0] - runs["auto"]["losses"][0]) < LOSS_TOL
    # at the full size on a chip "auto" is the kernel; "xla" never is
    checks["auto_step_holds_tpu_custom_call"] = \
        runs["auto"]["tpu_custom_call"]
    checks["xla_step_holds_no_custom_call"] = \
        not runs["xla"]["tpu_custom_call"]

    b = summary["bystander"]
    if b["outcome"] == "returned":
        _say(f"bystander: returned {b['sum']} in {b['seconds']:.1f} s from "
             f"pid {b['pid']}, computed on {b['platform']} "
             f"(JAX_PLATFORMS={b['jax_platforms']})")
    else:
        _say(f"bystander: {b['outcome']} after {b['seconds']:.1f} s"
             f"{': ' + b['error'] if 'error' in b else ''}")
    # a typed error would do; a hang, or an answer computed on the
    # trainer's chip, would not
    checks["bystander_did_not_hang"] = b["outcome"] != "hang"
    checks["bystander_stayed_off_the_chip"] = b.get("platform") != "tpu"

    holders = summary["device_library_holders"]
    _say(f"device library open in {len(holders)} process(es): "
         f"{holders}; the trainer's worker is pid {summary['worker_pid']}")
    checks["only_the_trainer_holds_the_device_library"] = (
        [h["pid"] for h in holders] == [summary["worker_pid"]])
    return {attention: r["compile_s"] for attention, r in runs.items()}


def _check_mesh(summary: dict, checks: dict) -> dict:
    """Print and judge the four-chip phase -> first-step seconds (which
    hold the compile) by run."""
    runs = summary["runs"]
    on_tpu = summary["device"]["platform"] == "tpu"
    for r in runs:
        step_s = statistics.median(r["step_s"])
        _say(f"mesh[{r['attention']}, data={r['devices']}, batch "
             f"{r['batch']}]: losses {[round(x, 4) for x in r['losses']]}; "
             f"first step {r['first_step_s']:.2f} s, then median "
             f"{step_s * 1e3:.1f} ms, {_rate(r, step_s, summary['device'])}")
        if r["devices"] == 4:
            _say(f"  shards: {r['shards']}")
    small = min(r["batch"] for r in runs)
    for attention in ("xla", "auto"):
        four, one = (next(r for r in runs if r["attention"] == attention
                          and r["batch"] == small and r["devices"] == n)
                     for n in (4, 1))
        checks[f"{attention}_sharded_losses_match_one_device"] = all(
            math.isfinite(a) and abs(a - b) < LOSS_TOL
            for a, b in zip(four["losses"], one["losses"]))
    sharded = [r for r in runs if r["devices"] == 4]
    checks["every_device_holds_a_batch_shard"] = all(
        len({s["device"] for s in r["shards"]}) == 4
        and all(s["rows"] == r["batch"] // 4 for s in r["shards"])
        for r in sharded)
    # only the chip reports its memory; there, an empty device is a fault
    checks["every_device_holds_bytes"] = not on_tpu or all(
        s["bytes_in_use"] for r in sharded for s in r["shards"])
    checks["large_batch_losses_finite_and_falling"] = all(
        _finite_and_falling(r["losses"]) for r in sharded
        if r["batch"] != small)
    return {f"{r['attention']}/data={r['devices']}/batch={r['batch']} "
            f"(first step)": r["first_step_s"] for r in runs}


def _cache_entries(path: str) -> int:
    try:
        return sum(1 for name in os.listdir(path) if name.endswith("-cache"))
    except FileNotFoundError:
        return 0


def _note_compile_times(cache_dir: str, phase: str, compile_s: dict) -> None:
    """Print this run's compile seconds next to the last run's, kept beside
    the cache they depend on: a warm cache shows as a drop."""
    path = os.path.join(cache_dir, "chip_smoke_last_compile.json")
    try:
        with open(path) as f:
            last = json.load(f)
    except (OSError, ValueError):
        last = {}
    for name, s in compile_s.items():
        before = last.get(phase, {}).get(name)
        _say(f"compile[{name}]: {s:.2f} s this run, "
             + ("no earlier run on this cache" if before is None else
                f"{before:.2f} s the run before "
                f"({'dropped' if s < before else 'did not drop'})"))
    last[phase] = compile_s
    os.makedirs(cache_dir, exist_ok=True)
    with open(path, "w") as f:
        json.dump(last, f)


def _dump_worker_logs(session_dir: str) -> None:
    logs = os.path.join(session_dir, "logs")
    for name in sorted(os.listdir(logs)):
        with open(os.path.join(logs, name), errors="replace") as f:
            tail = f.read()[-3000:]
        print(f"--- {name} (tail)\n{tail}", file=sys.stderr, flush=True)


def _out_of_time():
    print("chip_smoke: FAILED: wall budget of "
          f"{WALL_BUDGET_S:.0f} s exhausted", file=sys.stderr, flush=True)
    try:
        ray_tpu.shutdown()
    finally:
        os._exit(1)


def main(argv=None, *, size: str = "full") -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    chips = parser.parse_args(argv).chips

    from ray_tpu._private import native_sched, native_store
    from ray_tpu.air.config import RunConfig, ScalingConfig
    from ray_tpu.train import JaxTrainer

    watchdog = threading.Timer(WALL_BUDGET_S, _out_of_time)
    watchdog.daemon = True
    watchdog.start()

    cache_dir = place_compile_cache()  # workers inherit the variable
    _say(f"compile cache: {cache_dir} ({_cache_entries(cache_dir)} entries "
         f"at start)")
    _say(f"native library loaded: {native_store.available()}; native "
         f"scheduler in use: {native_sched.available()}")

    storage = tempfile.mkdtemp(prefix="chip_smoke_")
    checks, summary = {}, None
    ray_tpu.init(num_tpus=chips)
    try:
        result = JaxTrainer(
            train_loop if chips == 1 else mesh_loop,
            train_loop_config={"size": SIZES[size]},
            scaling_config=ScalingConfig(num_workers=1, use_tpu=True,
                                         chips_per_worker=chips),
            run_config=RunConfig(name="chip_smoke", storage_path=storage),
        ).fit()
        checks["fit_raised_nothing"] = result.error is None
        if result.error is not None:
            _say(f"fit() failed: {result.error}")
            _dump_worker_logs(ray_tpu._private.worker.global_worker
                              .node.session_dir)
        summary = (result.metrics or {}).get("summary")
    finally:
        ray_tpu.shutdown()
        shutil.rmtree(storage, ignore_errors=True)
        watchdog.cancel()

    checks["worker_reported_a_summary"] = summary is not None
    if summary is not None:
        device = summary["device"]
        _say(f"device, as the trainer's worker (pid {summary['worker_pid']})"
             f" reports it: {device}")
        checks["platform_is_tpu"] = device["platform"] == "tpu"
        checks["device_count"] = device["count"] == chips
        if "runs" not in summary:
            _say("no chip: the worker ran no phase")
        else:
            check = _check_train if chips == 1 else _check_mesh
            _note_compile_times(cache_dir, f"chips={chips},size={size}",
                                check(summary, checks))
            _say(f"compile cache: {_cache_entries(cache_dir)} entries at "
                 f"end")
    checks["driver_never_imported_jax"] = "jax" not in sys.modules

    for name, ok in checks.items():
        _say(f"check {name}: {'ok' if ok else 'FAILED'}")
    failed = [name for name, ok in checks.items() if not ok]
    if failed:
        print(f"chip_smoke: FAILED: {', '.join(failed)}", file=sys.stderr,
              flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
