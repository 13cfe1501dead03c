"""The selective scan's kernels on the chip: held against the chunked
``lax.scan`` on the same operands, then timed at a layer's size.

    python benches/ssm_scan.py --check-length 1024 --length 16384

``--check-length`` positions (0: skip) of ``--channels`` channels and
``--states`` states in float32: the kernels' output and six gradients
against ``impl="scan"``, the largest difference over the largest entry,
what interpret mode cannot show of the pipeline's writes. Then at
``--length`` positions with bfloat16 ``x``, ``B`` and ``C`` (the model's
operands): wall time of forward and of forward plus backward, and from a
trace of three calls the device time of one ``ssm_scan_fwd`` and one
``ssm_scan_bwd`` alone with what each needs to move and to compute
(``perfbench/metrics/ssm_scan_roofline_pct.needed``). One JSON line each.
"""

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--check-length", type=int, default=1024)
    parser.add_argument("--length", type=int, default=16384)
    parser.add_argument("--channels", type=int, default=5120)
    parser.add_argument("--states", type=int, default=16)
    parser.add_argument("--chunk", type=int, default=None)
    parser.add_argument("--reps", type=int, default=5)
    parser.add_argument("--impl", default="pallas",
                        help="what the check holds against the scan "
                             "(pallas_interpret: a rehearsal on the CPU)")
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.ssm import selective_scan

    def operands(length, dtype):
        keys = jax.random.split(jax.random.PRNGKey(length), 7)
        shape = (1, length, args.channels)
        return (jax.random.normal(keys[0], shape, dtype),
                jax.nn.softplus(jax.random.normal(keys[1], shape) - 4.0),
                -jnp.exp(jax.random.normal(keys[2], (args.channels,
                                                     args.states))),
                jax.random.normal(keys[3], (1, length, args.states), dtype),
                jax.random.normal(keys[4], (1, length, args.states), dtype),
                jax.random.normal(keys[5], (args.channels,)),
                jax.random.normal(keys[6], shape))

    def out_and_grads(impl):
        def fn(*xs):
            *ops, w = xs
            out, pull = jax.vjp(lambda *o: selective_scan(
                *o, chunk=args.chunk, impl=impl).astype(jnp.float32), *ops)
            return (out, *pull(w))
        return jax.jit(fn)

    device = jax.devices()[0].device_kind
    if args.check_length:
        xs = operands(args.check_length, jnp.float32)
        got, want = out_and_grads(args.impl)(*xs), out_and_grads("scan")(*xs)
        print(json.dumps({"check_length": args.check_length, "device": device,
                          "against_scan": {
            name: float(jnp.abs(a - b).max() / jnp.abs(b).max())
            for name, a, b in zip(("y", "dx", "ddelta", "dA", "dB", "dC",
                                   "dD"), got, want)}}), flush=True)

    *ops, w = operands(args.length, jnp.bfloat16)
    fwd = jax.jit(lambda *o: selective_scan(*o, chunk=args.chunk))
    both = jax.jit(jax.grad(lambda *o: (selective_scan(
        *o, chunk=args.chunk).astype(jnp.float32) * w).sum(),
        argnums=tuple(range(6))))

    def timed(fn):
        jax.block_until_ready(fn(*ops))
        start = time.perf_counter()
        for _ in range(args.reps):
            out = fn(*ops)
        jax.block_until_ready(out)
        return (time.perf_counter() - start) / args.reps * 1e3

    line = {"length": args.length, "channels": args.channels,
            "states": args.states, "device": device,
            "fwd_ms": round(timed(fwd), 3),
            "fwd_bwd_ms": round(timed(both), 3)}

    from perfbench import xplane
    from perfbench.metrics.ssm_scan_ms import KERNEL
    from perfbench.metrics.ssm_scan_roofline_pct import needed

    trace_dir = tempfile.mkdtemp()
    try:
        with jax.profiler.trace(trace_dir):
            for _ in range(3):
                out = both(*ops)
            jax.block_until_ready(out)
        traced = xplane.load(xplane.find_xplane(trace_dir))
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    found = {}
    for name, start, end in traced.ops.get(0, ()):
        kernel = KERNEL.match(name)
        if kernel:
            found.setdefault(kernel.group(1), []).append(
                (end - start, needed(name)))
    for kind, calls in found.items():
        line[f"ssm_scan_{kind}_kernel_ms"] = round(
            sum(ns for ns, _ in calls) / len(calls) / 1e6, 3)
        line[f"ssm_scan_{kind}_needed"] = calls[0][1]
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
