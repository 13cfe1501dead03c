"""perfbench/run.py — one run of one cell of BENCHMARK.json.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process that never imports jax: it reads the cell's configuration,
traffic and metric names from data files, starts the runtime a user starts
(``ray_tpu.init(num_tpus=<chips>)``), runs the benchmark's train loop
(perfbench/worker.py) through ``JaxTrainer(...).fit()``, reads the numbers
back from ``Result.metrics``, shuts the cluster down, has acknowledged
saves read back by a process of their own, and prints one JSON line last.
A run whose worker finds no ``tpu`` platform, or fewer chips than the cell
names, exits 1 and prints no result; nothing falls back to the CPU.

``--bench-file`` names another file of BENCHMARK.json's shape; one that
says ``"rehearsal": true`` (perfbench/tests/rehearsal.json) may run on the
CPU at toy sizes to rehearse the control flow, and says so on its line.
"""

from __future__ import annotations

_T_PROCESS_START = __import__("time").time()

import argparse
import json
import math
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

WALL_BUDGET_S = 1150.0  # the contract gives a compiling run 1200 s
READBACK_TIMEOUT_S = 240.0


def _say(msg: str) -> None:
    print(f"perfbench: {msg}", flush=True)


def _fail(msg: str) -> int:
    print(f"perfbench: FAILED: {msg}", file=sys.stderr, flush=True)
    return 1


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(bench: dict, workload: str) -> dict:
    """The cell's entry, configuration, traffic and metric names, from the
    names in the benchmark file."""
    from perfbench import traffic as traffic_mod

    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} (have {sorted(cells)})")
    cell = cells[workload]
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    here = lambda m: "workloads" not in m or workload in m["workloads"]
    return {
        "cell": cell,
        "model": _load_json(os.path.join(ROOT, config["file"])),
        "traffic": traffic_mod.load(traffic_mod.traffic_path(
            ROOT, bench.get("traffic_dir", "perfbench/traffic"),
            cell["traffic"])),
        "end_to_end": [m for m in bench["end_to_end"] if here(m)],
        "per_layer": [m for m in bench["per_layer"] if here(m)],
        "metrics_dir": bench.get("metrics_dir", "perfbench/metrics"),
        "rehearsal": bool(bench.get("rehearsal")),
    }


def _out_of_time():
    print(f"perfbench: FAILED: wall budget of {WALL_BUDGET_S:.0f} s "
          "exhausted", file=sys.stderr, flush=True)
    try:
        import ray_tpu

        ray_tpu.shutdown()
    finally:
        os._exit(1)


def _dump_worker_logs() -> None:
    import ray_tpu

    logs = os.path.join(
        ray_tpu._private.worker.global_worker.node.session_dir, "logs")
    for name in sorted(os.listdir(logs)):
        with open(os.path.join(logs, name), errors="replace") as f:
            tail = f.read()[-3000:]
        print(f"--- {name} (tail)\n{tail}", file=sys.stderr, flush=True)


def _read_back(summary: dict, trial_dir: str, storage: str) -> dict:
    """Acknowledged saves of the interval, read back in a CPU process."""
    expect = {"trial_dir": trial_dir, "leaf_paths": summary["leaf_paths"],
              "saves": [{"index": s["index"], "sums": s["sums"]}
                        for s in summary["saves"]]}
    path = os.path.join(storage, "expect.json")
    with open(path, "w") as f:
        json.dump(expect, f)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "readback.py"), path], env=env,
        capture_output=True, text=True, timeout=READBACK_TIMEOUT_S)
    if out.returncode != 0:
        raise RuntimeError(f"readback.py exited {out.returncode}: "
                           f"{out.stderr[-2000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def _phase_lines(summary: dict, t_init_done: float, t_fit: float) -> dict:
    """Seconds of each set-up phase, process start -> first measured
    boundary."""
    marks = ([("process_start", _T_PROCESS_START),
              ("ray_tpu_init", t_init_done), ("fit_called", t_fit)]
             + [tuple(m) for m in summary["phases"]]
             + [("first_measured_boundary", summary["t0_epoch"])])
    return {f"{a[0]}->{b[0]}": round(b[1] - a[1], 3)
            for a, b in zip(marks, marks[1:])}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    parser.add_argument("--bench-file", default="BENCHMARK.json")
    parser.add_argument("--keep-trace", default=None,
                        help="with --trace 1: copy the .xplane.pb here")
    args = parser.parse_args(argv)

    import ray_tpu
    from perfbench import traffic as traffic_mod, worker
    from ray_tpu._private.compile_cache import place_compile_cache
    from ray_tpu.air.config import RunConfig, ScalingConfig
    from ray_tpu.train import JaxTrainer

    bench = _load_json(os.path.join(ROOT, args.bench_file))
    loaded = load_cell(bench, args.workload)
    cell, model, traffic = loaded["cell"], loaded["model"], loaded["traffic"]
    chips = cell["chips"]
    if model["layout"]["chips"] != chips:
        return _fail(f"cell {cell['name']} names {chips} chips, its "
                     f"configuration's layout {model['layout']['chips']}")

    watchdog = threading.Timer(WALL_BUDGET_S, _out_of_time)
    watchdog.daemon = True
    watchdog.start()

    cache_dir = place_compile_cache()  # workers inherit the variable
    # On the four-chip host every process of the machine stalls for 7-14 s
    # while the worker opens the chips (40 GB of host memory mapped without
    # hugepages; my chip runs, PR 23): the raylet misses its heartbeats and
    # at the default 10 s the GCS declares the node dead. A deployment
    # setting, given to every cell alike; PERF.md section 7.
    os.environ.setdefault("RAY_TPU_node_death_timeout_s", "120")
    _say(f"cell {cell['name']}: config {cell['config']}, traffic "
         f"{cell['traffic']}, {chips} chip(s), seed {args.seed}, "
         f"{args.seconds:g} s, trace {args.trace}"
         + (" [REHEARSAL: not a benchmark run]" if loaded["rehearsal"] else ""))
    _say(f"compile cache: {cache_dir}")

    storage = tempfile.mkdtemp(prefix="perfbench_")
    loop_config = {
        "root": ROOT, "model": model, "traffic": traffic, "chips": chips,
        "seed": args.seed, "seconds": args.seconds, "trace": args.trace,
        "rehearsal": loaded["rehearsal"], "storage": storage,
        "trace_dir": os.path.join(storage, "trace"),
        "per_layer": [m["name"] for m in loaded["per_layer"]],
        "metrics_dir": loaded["metrics_dir"],
    }
    summary, error, trial_dir, readback = None, None, None, None
    ray_tpu.init(num_tpus=chips)
    t_init_done = time.time()
    try:
        datasets = None
        if traffic["feed"] == "dataset":
            from ray_tpu import data

            datasets = {"train": data.from_numpy(
                traffic_mod.dataset_blocks(args.seed, traffic,
                                           model["vocab_size"]),
                column="tokens")}
        else:
            loop_config["resident_tokens"] = traffic_mod.resident_tokens(
                args.seed, traffic, model["vocab_size"])
        loop_config["fit_called"] = t_fit = time.time()
        result = JaxTrainer(
            worker.train_loop, train_loop_config=loop_config,
            scaling_config=ScalingConfig(num_workers=1, use_tpu=True,
                                         chips_per_worker=chips),
            run_config=RunConfig(name="perfbench", storage_path=storage),
            datasets=datasets,
        ).fit()
        error = result.error
        if error is not None:
            _dump_worker_logs()
        summary = (result.metrics or {}).get("summary")
        trial_dir = result.path
    finally:
        ray_tpu.shutdown()
    try:
        if error is None and summary and summary.get("saves"):
            readback = _read_back(summary, trial_dir, storage)
        if args.keep_trace and summary and summary.get("xplane"):
            os.makedirs(os.path.dirname(os.path.abspath(args.keep_trace)),
                        exist_ok=True)
            shutil.copy(summary["xplane"]["path"], args.keep_trace)
    finally:
        shutil.rmtree(storage, ignore_errors=True)
        watchdog.cancel()

    if error is not None:
        return _fail(f"fit() failed: {error}")
    if summary is None:
        return _fail("the worker reported no summary")
    device = summary["device"]
    _say(f"device, as the trainer's worker (pid {summary.get('worker_pid')}) "
         f"reports it: {device}")
    if summary.get("refused"):
        return _fail(f"the cell needs {chips} tpu chip(s); nothing ran and "
                     "no metric is printed")
    if "jax" in sys.modules:
        return _fail("the driver imported jax")

    # -- what the last line cannot say ---------------------------------
    _say(f"set-up phases (s): {_phase_lines(summary, t_init_done, t_fit)}")
    _say(f"interval: {summary['periods']} period(s), {summary['steps']} "
         f"steps, {len(summary['saves'])} save(s), "
         f"{summary['interval_s']:.4f} s on the worker's clock; "
         f"{summary['tokens_per_step']} tokens a step")
    _say(f"first eight losses: "
         f"{[round(x, 4) for x in summary['first_losses']]}")
    _say(f"reference: {summary['comparison']}")
    _say(f"compiler's HBM plan for the step: "
         f"{summary['plan_bytes'] / 2**30:.3f} GiB a chip; allocator's peak "
         f"{summary['allocator_peak_bytes'] / 2**30:.3f} GiB; parameters "
         f"{summary['n_params']:,}")
    _say(f"the step was compiled or loaded {summary['step_compiles']} "
         "time(s) before its state came back in the shardings it went in")
    _say(f"compile cache entries: {summary['cache_entries'][0]} at start, "
         f"{summary['cache_entries'][1]} at end; compilations inside the "
         f"interval: {summary['compiles_inside_interval']}")
    for s in summary["saves"]:
        _say(f"save {s['index']} after step {s['after_step']}: stall "
             f"{s['stall_s']:.3f} s")
    if readback is not None:
        _say(f"read back: {readback}")
    if summary.get("xplane"):
        _say(f"trace: {summary['xplane']}")

    # -- correct, attempted, failed -------------------------------------
    saves_failed = readback["failed"] if readback else 0
    attempted = summary["steps"] + len(summary["saves"])
    failed = summary["nonfinite_steps"] + saves_failed
    checks = {
        "reference_within_tolerance":
            summary["comparison"]["within_tolerance"],
        "every_loss_finite": summary["nonfinite_steps"] == 0
            and all(math.isfinite(x) for x in summary["first_losses"]),
        "nothing_compiled_inside_the_interval":
            not summary["compiles_inside_interval"],
        "saves_read_back_equal": saves_failed == 0 and (
            readback is None or readback["read_back"] == len(summary["saves"])),
        "whole_periods": summary["periods"] >= 1,
        "device_is_what_the_cell_names": loaded["rehearsal"] or (
            device["platform"] == "tpu" and device["count"] == chips),
    }
    for name, ok in checks.items():
        _say(f"check {name}: {'ok' if ok else 'FAILED'}")

    # -- the metrics of this kind of run ----------------------------------
    metrics = {}
    if args.trace:
        units = {m["name"]: m["unit"] for m in loaded["per_layer"]}
        for name, value in summary["per_layer"].items():
            metrics[name] = {"value": value, "unit": units[name]}
    else:
        rate = summary["tokens"] / summary["interval_s"] / chips
        for m in loaded["end_to_end"]:
            if m["name"] == "setup_s":
                value = summary["t0_epoch"] - _T_PROCESS_START
            elif m["name"].endswith("tokens_per_s_per_chip"):
                value = rate  # one quantity, named by cell where bounds differ
            else:
                return _fail(f"no way to measure end-to-end metric "
                             f"{m['name']!r}")
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    out_device = dict(device)
    # the allocator's peak does not count a program's temporaries on this
    # runtime (PERF.md section 7): the step's plan is the lower bound
    out_device["memory_peak_bytes"] = max(summary["allocator_peak_bytes"],
                                          summary["plan_bytes"])
    line = {"correct": all(checks.values()), "attempted": attempted,
            "failed": failed, "metrics": metrics, "device": out_device}
    if args.trace:
        if summary.get("busy_s") is not None:
            out_device["busy_s"] = summary["busy_s"]
            out_device["window_s"] = summary["window_s"]
        if summary.get("breakdown"):
            line["breakdown"] = summary["breakdown"]
    if loaded["rehearsal"]:
        line["rehearsal"] = True
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
