"""The start-up path's account, read without the benchmark's readers.

Two things `perfbench/run.py` cannot print (it is the benchmark's file):

    python benches/start_account.py inits --calls 10 [--chips 1]

calls ``ray_tpu.init(num_tpus=<chips>)`` / ``shutdown()`` in ``--calls``
fresh processes, one after another, and prints one JSON line a call: the
driver's ``init`` with its three children, what the GCS process recorded
of its own start (``gcs/boot``, ``gcs/server``) and each worker the raylet
started ahead (``worker/boot``) on the driver's clock as seconds after
``init`` was entered, the imports before ``init`` and
``shutdown``. It needs no chip: which child holds the difference between
``init``'s fast mode and its slow one is read from its lines.

    python benches/start_account.py run --out <dir> -- --workload <cell> \
        --seed <n> --seconds 32 --trace 1

is one run of the benchmark's command in this process, with the cluster's
merged rings written to ``<dir>/<cell>_<seed>_rings.json`` and the train
timeline (``steptrace.chrome_trace``, what ``ray_tpu train timeline``
writes) to ``<dir>/<cell>_<seed>_timeline.json`` just before the cluster
is shut down.
"""

from __future__ import annotations

_T_PROCESS_START = __import__("time").time()

import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CHILD_PREFIXES = ("gcs/", "worker/")


def one_init(chips: int) -> dict:
    import ray_tpu
    from ray_tpu.util import state

    imported = time.time()
    ray_tpu.init(num_tpus=chips)
    returned = time.time()
    merged = state.steptrace_summary()
    entered = next(p["start"] for p in merged["phases"]
                   if p["phase"] == "init")
    spans = {}
    for p in merged["phases"]:
        if p["phase"].startswith(("init",) + CHILD_PREFIXES):
            spans.setdefault(p["phase"], []).append(
                [round(p["start"] - entered, 3),
                 round(p["end"] - p["start"], 3)])
    ray_tpu.shutdown()
    return {"imports_s": round(imported - _T_PROCESS_START, 3),
            "init_from_outside_s": round(returned - imported, 3),
            "shutdown_s": round(time.time() - returned, 3),
            "spans_after_init_entered": spans,
            "errors": merged.get("errors")}


def inits(calls: int, chips: int) -> int:
    for i in range(calls):
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "one-init",
             "--chips", str(chips)], capture_output=True, text=True,
            timeout=300)
        line = out.stdout.strip().splitlines()[-1:] or [""]
        if out.returncode != 0 or not line[0].startswith("{"):
            print(f"call {i} failed ({out.returncode}): "
                  f"{out.stderr[-2000:]}", file=sys.stderr, flush=True)
            return 1
        print(json.dumps({"call": i, **json.loads(line[0])}), flush=True)
    return 0


def run(out_dir: str, argv: list) -> int:
    import ray_tpu
    from perfbench import run as bench_run
    from ray_tpu._private import steptrace
    from ray_tpu.util import state

    def value(flag):
        return argv[argv.index(flag) + 1]

    stem = os.path.join(out_dir, f"{value('--workload')}_{value('--seed')}")
    os.makedirs(out_dir, exist_ok=True)
    shutdown = ray_tpu.shutdown

    def dump_then_shutdown():
        try:
            merged = state.steptrace_summary()
            with open(stem + "_rings.json", "w") as f:
                json.dump({k: merged.get(k) for k in (
                    "phases", "restarts", "rings", "errors")}, f)
            with open(stem + "_timeline.json", "w") as f:
                json.dump(steptrace.chrome_trace(merged), f)
        finally:
            shutdown()

    ray_tpu.shutdown = dump_then_shutdown
    try:
        return bench_run.main(argv)
    finally:
        ray_tpu.shutdown = shutdown


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = parser.add_subparsers(dest="what", required=True)
    for name in ("inits", "one-init"):
        p = sub.add_parser(name)
        p.add_argument("--calls", type=int, default=10)
        p.add_argument("--chips", type=int, default=1)
    p = sub.add_parser("run")
    p.add_argument("--out", required=True)
    p.add_argument("argv", nargs=argparse.REMAINDER)
    args = parser.parse_args()
    if args.what == "one-init":
        print(json.dumps(one_init(args.chips)), flush=True)
        return 0
    if args.what == "inits":
        return inits(args.calls, args.chips)
    return run(args.out, [a for a in args.argv if a != "--"])


if __name__ == "__main__":
    sys.exit(main())
