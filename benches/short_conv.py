"""The gated short convolution's kernels on the chip: held against the
``jnp`` form on the same operands, then both timed at a layer's shapes.

    python benches/short_conv.py --shapes 4x8192,1x32768 --out chiprun_out/pr52

For each ``batch x length`` of ``--channels`` channels: in float32 and in
bfloat16 the kernels' ``y``, ``dbcx`` and ``dtaps`` against ``impl="jnp"``
(the largest difference over the largest entry: what interpret mode cannot
show of the rotations and of the pipeline's writes), then in bfloat16 the
wall time of forward and of forward plus backward by each form (the ``jnp``
form is what XLA makes of the padded slices), and from a trace of three
calls the device time of one ``short_conv_fwd`` and one ``short_conv_bwd``
alone with what each needs to move
(``perfbench/metrics/short_conv_roofline_pct.needed``) and the time of every
operation XLA's form runs. One JSON line a shape, also appended to
``<out>/short_conv.jsonl``.
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
    parser.add_argument("--shapes", default="4x8192,1x32768")
    parser.add_argument("--channels", type=int, default=2048)
    parser.add_argument("--reps", type=int, default=10)
    parser.add_argument("--impl", default="pallas",
                        help="what is held against the jnp form "
                             "(pallas_interpret: a rehearsal on the CPU)")
    parser.add_argument("--out", default=None)
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp

    from perfbench import xplane
    from perfbench.metrics.short_conv_ms import KERNEL
    from perfbench.metrics.short_conv_roofline_pct import needed
    from ray_tpu.ops.conv import gated_short_conv

    device = jax.devices()[0].device_kind
    h = args.channels

    def operands(batch, length, dtype):
        keys = jax.random.split(jax.random.PRNGKey(batch * length), 3)
        return (jax.random.normal(keys[0], (batch, length, 3 * h), dtype),
                jax.random.normal(keys[1], (3, h)),
                jax.random.normal(keys[2], (batch, length, h), dtype))

    def out_and_grads(impl):
        def fn(bcx, taps, dy):
            y, pull = jax.vjp(
                lambda a, w: gated_short_conv(a, w, impl=impl), bcx, taps)
            return (y, *pull(dy))
        return jax.jit(fn)

    def timed(fn, *xs):
        jax.block_until_ready(fn(*xs))
        start = time.perf_counter()
        for _ in range(args.reps):
            out = fn(*xs)
        jax.block_until_ready(out)
        return round((time.perf_counter() - start) / args.reps * 1e3, 3)

    def device_ops(fn, *xs):
        """[(HLO text, ns)] of chip 0 over three traced calls."""
        trace_dir = tempfile.mkdtemp()
        try:
            with jax.profiler.trace(trace_dir):
                for _ in range(3):
                    out = fn(*xs)
                jax.block_until_ready(out)
            traced = xplane.load(xplane.find_xplane(trace_dir))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        return [(name, end - start)
                for name, start, end in traced.ops.get(0, ())]

    for shape in args.shapes.split(","):
        batch, length = (int(n) for n in shape.split("x"))
        line = {"batch": batch, "length": length, "channels": h,
                "device": device, "against_jnp": {}}
        for dtype in (jnp.float32, jnp.bfloat16):
            xs = operands(batch, length, dtype)
            got = out_and_grads(args.impl)(*xs)
            want = out_and_grads("jnp")(*xs)
            line["against_jnp"][jnp.dtype(dtype).name] = {
                name: float(jnp.abs(a.astype(jnp.float32)
                                    - b.astype(jnp.float32)).max()
                            / jnp.abs(b.astype(jnp.float32)).max())
                for name, a, b in zip(("y", "dbcx", "dtaps"), got, want)}
        xs = operands(batch, length, jnp.bfloat16)
        for impl in (args.impl, "jnp"):
            fwd = jax.jit(lambda a, w, impl=impl: gated_short_conv(
                a, w, impl=impl))
            both = out_and_grads(impl)
            line[f"{impl}_fwd_ms"] = timed(fwd, *xs[:2])
            line[f"{impl}_fwd_bwd_ms"] = timed(both, *xs)
            ops = device_ops(both, *xs)
            if impl == "jnp":
                by_op = {}
                for name, ns in ops:
                    op = xplane.short_name(name)
                    by_op[op] = by_op.get(op, 0) + ns / 3e6
                line["jnp_fwd_bwd_device_ms"] = round(sum(by_op.values()), 3)
                line["jnp_ops_ms"] = {op: round(ms, 3) for op, ms in sorted(
                    by_op.items(), key=lambda kv: -kv[1])[:12]}
                continue
            found = {}
            for name, ns in ops:
                kernel = KERNEL.match(name)
                if kernel:
                    found.setdefault(kernel.group(1), []).append(
                        (ns, needed(name)))
            for kind, calls in found.items():
                ms = sum(ns for ns, _ in calls) / len(calls) / 1e6
                line[f"short_conv_{kind}_kernel_ms"] = round(ms, 3)
                line[f"short_conv_{kind}_needed"] = calls[0][1]
                line[f"short_conv_{kind}_gb_per_s"] = round(
                    calls[0][1]["bytes"] / ms / 1e6, 1)
        print(json.dumps(line), flush=True)
        if args.out:
            os.makedirs(args.out, exist_ok=True)
            with open(os.path.join(args.out, "short_conv.jsonl"), "a") as f:
                f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
