"""Jobs back to back on one host: one cell run N times from given trees, each
run started as the one before returns, NO pause. What the driver's check
does with a parent and a change, and what a user does who runs a sweep or a
resume: on a v5e 2x2 host the kernel takes 9 to 16 s to release a dead
worker's four chips, and a job that opens them inside that window dies with
``open(/dev/vfio/<n>): Device or resource busy`` (PERF.md section 7).

    git archive --prefix=.export/parent/ <parent> | tar -x
    git archive --prefix=.export/change/ $(git write-tree) | tar -x
    python benches/back_to_back.py --trees parent=.export/parent \\
        change=.export/change --order parent,change,parent,change \\
        --workload gpt2-xl.step-fsdp4 --out chiprun_out/back_to_back

Prints one JSON line a run: the tree, the exit code, the wall time, the
wait the gang logged before it opened the chips (``gang_waited_s``; null
from a tree whose gang does not wait), the result line's ``correct`` and
end-to-end metrics, and, the moment the run returned, which chip nodes
opened at once (``held_at_return``: the nodes that did not) beside the
holders /proc showed for them. Every run's output is kept under ``--out``.
The nodes are probed once a return and never while a run is live: this
process's open would hand the run's own open the same EBUSY.

``--workload open-only`` runs the smallest job that opens the chips (this
file's ``--job``: ``ray_tpu.init``, ``JaxTrainer.fit()`` of a loop that
lists the devices and multiplies two matrices, ``ray_tpu.shutdown()``) in
place of ``perfbench/run.py``. ``--watch S`` keeps probing for up to S
seconds after each return, until every node opens, and prints when each
did and for how long /proc named a holder: a pause, so not the driver's
sequence, but what tells the two probes apart.
"""

import argparse
import json
import os
import re
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WAITED = re.compile(r"waited ([0-9.]+) s for this host's chips")


def job(chips: int) -> int:
    """The ``open-only`` job, run with the tree under test on PYTHONPATH."""
    import tempfile

    import ray_tpu
    from ray_tpu.air.config import RunConfig, ScalingConfig
    from ray_tpu.train import JaxTrainer

    def loop(config):
        import jax
        import jax.numpy as jnp

        from ray_tpu import train

        devices = jax.devices()
        x = jnp.ones((1024, 1024), jnp.bfloat16)
        train.report({"platform": devices[0].platform, "count": len(devices),
                      "sum": float(jnp.sum(x @ x))})

    ray_tpu.init(num_tpus=chips)
    try:
        result = JaxTrainer(
            loop,
            scaling_config=ScalingConfig(num_workers=1, use_tpu=True,
                                         chips_per_worker=chips),
            run_config=RunConfig(name="open_only",
                                 storage_path=tempfile.mkdtemp()),
        ).fit()
    finally:
        ray_tpu.shutdown()
    if result.error is not None:
        print(f"open-only: fit() failed: {result.error}", file=sys.stderr)
        return 1
    m = result.metrics
    print(json.dumps({"correct": m["platform"] == "tpu" and m["count"] == chips,
                      "device": {"platform": m["platform"], "count": m["count"]}}))
    return 0 if m["platform"] == "tpu" else 1


def probe(nodes):
    """One pass, the moment a run returned: {node: [held, /proc's holders]}."""
    from ray_tpu.train.backend import _chip_holders, _chip_is_held

    return {n: [_chip_is_held(n), _chip_holders(n)] for n in nodes}


def watch(nodes, first, returned_at, seconds):
    """Keep probing until every node opens: when each did, and until when
    /proc still named a holder for it."""
    opened = {n: 0.0 for n in nodes if not first[n][0]}
    named_until = {n: (0.0 if first[n][1] else None) for n in nodes}
    while len(opened) < len(nodes) and time.monotonic() - returned_at < seconds:
        time.sleep(0.25)
        at = round(time.monotonic() - returned_at, 2)
        for n, (held, holders) in probe(nodes).items():
            if holders:
                named_until[n] = at
            if not held:
                opened.setdefault(n, at)
    return {"opened_after_s": {n: opened.get(n) for n in nodes},
            "proc_named_a_holder_until_s": named_until}


def gang_waited(texts, session_root, since):
    """The gang's own line, from the run's output or, where the stream to
    the driver was cut by the shutdown, from the session's worker logs."""
    logs = []
    if os.path.isdir(session_root):
        for session in os.listdir(session_root):
            d = os.path.join(session_root, session, "logs")
            if os.path.isdir(d) and os.path.getmtime(d) >= since:
                logs += [os.path.join(d, f) for f in os.listdir(d)
                         if f.startswith("worker-")]
    for path in logs:
        with open(path, errors="replace") as f:
            texts.append(f.read())
    found = [float(m) for t in texts for m in WAITED.findall(t)]
    return max(found) if found else None


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--job", type=int, default=0, help=argparse.SUPPRESS)
    parser.add_argument("--trees", nargs="+", default=[f"change={REPO}"],
                        metavar="NAME=DIR")
    parser.add_argument("--order", default=None,
                        help="names from --trees, comma-separated, one a run")
    parser.add_argument("--workload", default="open-only")
    parser.add_argument("--chips", type=int, default=None,
                        help="of open-only; default: every chip node found")
    parser.add_argument("--seconds", type=float, default=32)
    parser.add_argument("--seed", type=int, default=2147490300)
    parser.add_argument("--watch", type=float, default=0)
    parser.add_argument("--out", default="chiprun_out/back_to_back")
    args = parser.parse_args()
    if args.job:
        return job(args.job)

    sys.path.insert(0, REPO)
    from ray_tpu._private.node import DEFAULT_SESSION_ROOT
    from ray_tpu._private.resource_spec import tpu_device_nodes

    trees = dict(t.split("=", 1) for t in args.trees)
    order = args.order.split(",") if args.order else list(trees)
    nodes = tpu_device_nodes()
    os.makedirs(args.out, exist_ok=True)
    print(json.dumps({"nodes": nodes, "held_at_start": [
        n for n, (held, _) in probe(nodes).items() if held]}), flush=True)
    failed = 0
    for i, name in enumerate(order, 1):
        tree = os.path.abspath(trees[name])
        if args.workload == "open-only":
            cmd = [sys.executable, os.path.abspath(__file__), "--job",
                   str(args.chips or len(nodes) or 1)]
        else:
            cmd = [sys.executable, "perfbench/run.py", "--workload",
                   args.workload, "--seed", str(args.seed + i), "--seconds",
                   str(args.seconds), "--trace", "0"]
        stem = os.path.join(args.out, f"{i}_{name}")
        env = dict(os.environ, PYTHONPATH=tree)
        started, start = time.time(), time.monotonic()
        with open(stem + ".out", "w") as out, open(stem + ".err", "w") as err:
            rc = subprocess.call(cmd, cwd=tree, env=env, stdout=out, stderr=err)
        returned_at = time.monotonic()
        first = probe(nodes)
        line = {"run": i, "tree": name, "rc": rc,
                "wall_s": round(returned_at - start, 2),
                "held_at_return": [n for n in nodes if first[n][0]],
                "proc_holders_at_return": {n: first[n][1] for n in nodes
                                           if first[n][1]}}
        if args.watch:
            line.update(watch(nodes, first, returned_at, args.watch))
        texts = [open(stem + ext, errors="replace").read()
                 for ext in (".out", ".err")]
        last = texts[0].strip().splitlines()[-1:] or [""]
        try:
            result = json.loads(last[0])
            line["correct"] = result.get("correct")
            line.update({k: v["value"] for k, v in
                         result.get("metrics", {}).items()})
        except ValueError:
            line["correct"] = None
        line["gang_waited_s"] = gang_waited(
            texts, DEFAULT_SESSION_ROOT or "", started - 1)
        failed += rc != 0
        print(json.dumps(line), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
