"""The scalar-decay state-space scan's kernels on the chip: held against the
chunked twin and the plain recurrence on the same operands, then timed at a
layer's size.

    python benches/ssd_scan.py --shape 2x8192 --out chiprun_out/pr58

At ``batch x length`` of ``--heads`` heads of 64 over ``--groups`` groups of
``--states`` states: in float32 and in bfloat16 (at ``--check-length``) the
kernels' ``y`` and six gradients (x, dt, A, B, C, D) against ``impl="scan"``
and against the recurrence one position a step
(``perfbench/families/nemotron_h_reference.ssd_recurrence`` in float32 at
precision highest; the norm of the difference over the norm: what interpret
mode cannot show of the pipeline's writes and of the MXU's rounding), then
in bfloat16 the wall time of forward and of forward plus backward by the
kernels and by the twin, and from a trace of three calls the device time of
one ``ssd_fwd`` and one ``ssd_bwd`` alone with what each needs
(``perfbench/metrics/ssd_roofline_pct.needed``) and its share of that
floor. One JSON line each, also appended to ``<out>/ssd_scan.jsonl``.
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
    parser.add_argument("--shape", default="2x8192")
    parser.add_argument("--heads", type=int, default=64)
    parser.add_argument("--groups", type=int, default=8)
    parser.add_argument("--states", type=int, default=128)
    parser.add_argument("--check-length", type=int, default=2048)
    parser.add_argument("--reps", type=int, default=5)
    parser.add_argument("--check", type=int, default=1)
    parser.add_argument("--twin", type=int, default=1,
                        help="0: do not time the twin")
    parser.add_argument("--impl", default="pallas",
                        help="what is held against the twin and timed "
                             "(pallas_interpret: a rehearsal on the CPU)")
    parser.add_argument("--out", default=None)
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp

    from perfbench import xplane
    from perfbench.families.nemotron_h_reference import ssd_recurrence
    from perfbench.metrics.ssd_ms import KERNEL
    from perfbench.metrics.ssd_roofline_pct import needed
    from ray_tpu.ops.ssm import ssd_scan

    device = jax.devices()[0].device_kind
    batch, length = (int(n) for n in args.shape.split("x"))
    f32, head_dim = jnp.float32, 64

    def emit(line):
        print(json.dumps(line), flush=True)
        if args.out:
            os.makedirs(args.out, exist_ok=True)
            with open(os.path.join(args.out, "ssd_scan.jsonl"), "a") as f:
                f.write(json.dumps(line) + "\n")

    def operands(dtype, length):
        """What a layer hands the scan at initialisation: x, B, C after a
        SiLU, dt a softplus near 0.001 .. 0.1, A = -(1 .. heads), D = 1."""
        ks = jax.random.split(jax.random.PRNGKey(batch * length), 6)
        xs = (batch, length, args.heads, head_dim)
        bc = (batch, length, args.groups, args.states)
        silu = lambda k, shape: jax.nn.silu(
            jax.random.normal(k, shape)).astype(dtype)
        dt = jnp.exp(jax.random.uniform(ks[1], xs[:3], f32, -6.9, -2.3))
        return (silu(ks[0], xs), dt,
                -jnp.arange(1, args.heads + 1, dtype=f32),
                silu(ks[2], bc), silu(ks[3], bc),
                jnp.ones((args.heads,), f32), jax.random.normal(ks[4], xs))

    def out_and_grads(scan):
        def fn(*xs):
            *ops, w = xs
            out, pull = jax.vjp(lambda *o: scan(*o).astype(f32), *ops)
            return (out, *pull(w))
        return jax.jit(fn)

    by_impl = lambda impl: (lambda *o: ssd_scan(*o, impl=impl))

    def plain(x, dt, a, b, c, skip):
        with jax.default_matmul_precision("highest"):
            return ssd_recurrence(x.astype(f32), dt, a, b.astype(f32),
                                  c.astype(f32), skip, remat=True)

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

    names = ("y", "dx", "ddt", "dA", "dB", "dC", "dD")
    off = lambda a, b: float(jnp.linalg.norm(a.astype(f32) - b.astype(f32))
                             / jnp.linalg.norm(b.astype(f32)))
    shape = {"batch": batch, "heads": args.heads, "groups": args.groups,
             "head_dim": head_dim, "states": args.states, "device": device}
    if args.check:
        line = dict(shape, length=args.check_length)
        for dtype in (jnp.float32, jnp.bfloat16):
            xs = operands(dtype, args.check_length)
            got = out_and_grads(by_impl(args.impl))(*xs)
            twin = out_and_grads(by_impl("scan"))(*xs)
            want = out_and_grads(plain)(*xs)
            key = jnp.dtype(dtype).name
            line[f"{key}_against_recurrence"] = {
                n: off(a, b) for n, a, b in zip(names, got, want)}
            line[f"{key}_against_scan"] = {
                n: off(a, b) for n, a, b in zip(names, got, twin)}
            line[f"{key}_scan_against_recurrence"] = {
                n: off(a, b) for n, a, b in zip(names, twin, want)}
            del got, twin, want
        emit(line)

    peaks = json.load(open(os.path.join(os.path.dirname(
        os.path.abspath(xplane.__file__)), "peaks.json")))["by_device_kind"]
    xs = operands(jnp.bfloat16, length)
    line = dict(shape, length=length, dtype="bfloat16")
    for impl in (args.impl, "scan") if args.twin else (args.impl,):
        fwd, both = jax.jit(by_impl(impl)), out_and_grads(by_impl(impl))
        line[f"{impl}_fwd_ms"] = timed(fwd, *xs[:6])
        line[f"{impl}_fwd_bwd_ms"] = timed(both, *xs)
        if impl == "scan":
            continue
        found = {}
        for name, ns in device_ops(both, *xs):
            kernel = KERNEL.match(name)
            if kernel:
                found.setdefault(kernel.group(1), []).append(
                    (ns, needed(name)))
        for kind, calls in found.items():
            ms = sum(ns for ns, _ in calls) / len(calls) / 1e6
            need = calls[0][1]
            line[f"ssd_{kind}_kernel_ms"] = round(ms, 3)
            line[f"ssd_{kind}_needed"] = need
            if need and device in peaks:
                least = max(
                    need["bytes"] / peaks[device]["hbm_bytes_per_s"],
                    need["flops"] / peaks[device]["bf16_flops_per_s"])
                line[f"ssd_{kind}_roofline_pct"] = round(
                    100 * least * 1e3 / ms, 2)
    emit(line)


if __name__ == "__main__":
    main()
