"""The gated delta rule's kernels on the chip: held against the chunked
``lax.scan`` twin and the plain recurrence on the same operands, then timed
at a layer's size; and the plain causal convolution's kernels beside them.

    python benches/delta_rule.py --shape 4x8192 --out chiprun_out/pr56
    python benches/delta_rule.py --shape 2x8192 --rule 0 --out chiprun_out/pr57
    python benches/delta_rule.py --shape 2x8192 --conv 0 --out chiprun_out/pr59
    python benches/delta_rule.py --shape 2x8192 --conv 0 --heads 16    # rep 1
    python benches/delta_rule.py --shape 2x8192 --conv 0 --heads 64    # rep 4

At ``batch x length`` of ``--key-heads`` / ``--heads`` heads ``--d-k`` x
``--d-v`` wide: in float32 and in bfloat16 the kernels' ``o`` and five
gradients against ``impl="scan"`` and against the recurrence one position a
step (``perfbench/families/qwen3_next_reference.delta_rule`` in float32 at
precision highest; the norm of the difference over the norm: what interpret
mode cannot show of the pipeline's writes and of the MXU's rounding), then
in bfloat16 the wall time of forward and of forward plus backward by the
kernels and by the twin, and from a trace of three calls the device time of
one ``gated_delta_fwd`` and one ``gated_delta_bwd`` alone with what each
needs (``perfbench/metrics/delta_rule_roofline_pct.needed``) and its share
of that floor, and from the two kernels' own jaxprs what a grid step is: the
grid, the ``dot_general``s a step, the positions x value heads a step and so
the ``dot_general``s a chunk and head (``gated_delta_{fwd,bwd}_text``: the
program's text, no measurement; ``--heads`` on ``--key-heads`` gives the
value heads a key head, 1, 2 or 4 in the examples above) (``--rule 0`` skips
all of that). ``--conv 1``:
``ops.conv.causal_conv`` with four taps and a SiLU at ``--conv-channels``
channels. With ``--check 1`` the kernels (``causal_conv_fwd`` /
``causal_conv_bwd``) and XLA's form, in float32 and bfloat16, against a
float32 loop of one position a step on the chip: ``y``, ``dx`` and ``dtaps``,
the norm of the difference over the norm (interpret mode cannot show the
pipeline's writes, nor the backward's walk from a sequence's end). Then XLA's
form and the kernels timed forward and forward plus backward, with the device
time of every operation, each kernel's own against
``ops.conv.causal_needed_bytes`` and its share of that floor. One JSON line each, also appended to
``<out>/delta_rule.jsonl``.
"""

import argparse
import json
import math
import os
import re
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--shape", default="4x8192")
    parser.add_argument("--key-heads", type=int, default=16)
    parser.add_argument("--heads", type=int, default=32)
    parser.add_argument("--d-k", type=int, default=128)
    parser.add_argument("--d-v", type=int, default=128)
    parser.add_argument("--chunk", type=int, default=None)
    parser.add_argument("--reps", type=int, default=5)
    parser.add_argument("--check", type=int, default=1)
    parser.add_argument("--rule", type=int, default=1,
                        help="0: the convolution alone")
    parser.add_argument("--twin", type=int, default=1,
                        help="0: do not time the chunked lax.scan")
    parser.add_argument("--conv", type=int, default=1)
    parser.add_argument("--conv-channels", type=int, default=8192)
    parser.add_argument("--impl", default="pallas",
                        help="what is held against the twin and timed "
                             "(pallas_interpret: a rehearsal on the CPU)")
    parser.add_argument("--out", default=None)
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp

    from perfbench import xplane
    from perfbench.families.qwen3_next_reference import delta_rule
    from perfbench.metrics.delta_rule_ms import KERNEL
    from perfbench.metrics.delta_rule_roofline_pct import needed
    from ray_tpu.ops import conv
    from ray_tpu.ops.delta import gated_delta_rule

    CONV_KERNEL = re.compile(r'causal_conv_(fwd|bwd)[\w.\-]* = .*'
                             r'custom_call_target="tpu_custom_call"')
    device = jax.devices()[0].device_kind
    batch, length = (int(n) for n in args.shape.split("x"))
    f32 = jnp.float32

    def emit(line):
        print(json.dumps(line), flush=True)
        if args.out:
            os.makedirs(args.out, exist_ok=True)
            with open(os.path.join(args.out, "delta_rule.jsonl"), "a") as f:
                f.write(json.dumps(line) + "\n")

    def operands(dtype):
        """What a layer hands the rule at initialisation: unit q and k (q
        scaled), beta a sigmoid, g = -A softplus(a + 1), A uniform (0, 16)."""
        ks = jax.random.split(jax.random.PRNGKey(batch * length), 7)
        unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
        qk = (batch, length, args.key_heads, args.d_k)
        vo = (batch, length, args.heads, args.d_v)
        gate = vo[:3]
        rate = jax.random.uniform(ks[5], (args.heads,), f32, 1e-4, 16.0)
        return ((unit(jax.random.normal(ks[0], qk)) * args.d_k ** -0.5
                 ).astype(dtype),
                unit(jax.random.normal(ks[1], qk)).astype(dtype),
                jax.random.normal(ks[2], vo).astype(dtype),
                -rate * jax.nn.softplus(jax.random.normal(ks[3], gate) + 1.0),
                jax.nn.sigmoid(jax.random.normal(ks[4], gate)),
                jax.random.normal(ks[6], vo))

    def out_and_grads(rule):
        def fn(*xs):
            *ops, w = xs
            out, pull = jax.vjp(lambda *o: rule(*o).astype(f32), *ops)
            return (out, *pull(w))
        return jax.jit(fn)

    by_impl = lambda impl: (lambda *o: gated_delta_rule(
        *o, chunk=args.chunk, impl=impl))

    def plain(q, k, v, g, beta):
        with jax.default_matmul_precision("highest"):
            return delta_rule(*(t.astype(f32) for t in (q, k, v, g, beta)),
                              remat=True)

    def timed(fn, *xs):
        jax.block_until_ready(fn(*xs))
        start = time.perf_counter()
        for _ in range(args.reps):
            out = fn(*xs)
        jax.block_until_ready(out)
        return round((time.perf_counter() - start) / args.reps * 1e3, 3)

    def kernel_text(fn, *xs):
        """{"fwd" | "bwd": what one grid step of the kernel is, read from its
        own jaxpr}: the grid, its steps, the ``dot_general``s a step (a
        ``pl.when`` body counted as written), the positions x value heads a
        step works on and so the ``dot_general``s a chunk and head."""
        found = {}

        def dots(jaxpr):
            return sum((eqn.primitive.name == "dot_general") + sum(
                map(dots, jax.core.jaxprs_in_params(eqn.params)))
                for eqn in jaxpr.eqns)

        def walk(jaxpr):
            for eqn in jaxpr.eqns:
                name = eqn.params.get("name", "")
                if eqn.primitive.name == "pallas_call" and \
                        name.startswith("gated_delta_"):
                    grid = tuple(eqn.params["grid_mapping"].grid)
                    steps = math.prod(grid)
                    cells = batch * length * args.heads // steps
                    made = dots(eqn.params["jaxpr"])
                    found[name.rsplit("_", 1)[1]] = {
                        "grid": grid, "grid_steps": steps,
                        "dot_generals_a_step": made,
                        "positions_x_heads_a_step": cells,
                        "dot_generals_a_chunk_and_head": round(
                            made * (args.chunk or 64) / cells, 2)}
                else:
                    for sub in jax.core.jaxprs_in_params(eqn.params):
                        walk(sub)

        walk(jax.make_jaxpr(fn)(*xs).jaxpr)
        return found

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

    names = ("o", "dq", "dk", "dv", "dg", "dbeta")
    off = lambda a, b: float(jnp.linalg.norm(a.astype(f32) - b.astype(f32))
                             / jnp.linalg.norm(b.astype(f32)))
    if args.check and args.rule:
        line = {"batch": batch, "length": length, "heads": args.heads,
                "key_heads": args.key_heads, "d_k": args.d_k, "d_v": args.d_v,
                "device": device}
        for dtype in (jnp.float32, jnp.bfloat16):
            xs = operands(dtype)
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
    if args.rule:
        xs = operands(jnp.bfloat16)
        line = {"batch": batch, "length": length, "heads": args.heads,
                "key_heads": args.key_heads, "d_k": args.d_k, "d_v": args.d_v,
                "dtype": "bfloat16", "device": device}
        for impl in (args.impl, "scan")[:1 + bool(args.twin)]:
            fwd, both = jax.jit(by_impl(impl)), out_and_grads(by_impl(impl))
            line[f"{impl}_fwd_ms"] = timed(fwd, *xs[:5])
            line[f"{impl}_fwd_bwd_ms"] = timed(both, *xs)
            if impl == "scan":
                continue
            for kind, text in kernel_text(both, *xs).items():
                line[f"gated_delta_{kind}_text"] = text
            found = {}
            for name, ns in device_ops(both, *xs):
                kernel = KERNEL.match(name)
                if kernel:
                    found.setdefault(kernel.group(1), []).append(
                        (ns, needed(name)))
            for kind, calls in found.items():
                ms = sum(ns for ns, _ in calls) / len(calls) / 1e6
                need = calls[0][1]
                line[f"gated_delta_{kind}_kernel_ms"] = round(ms, 3)
                line[f"gated_delta_{kind}_needed"] = need
                if need and device in peaks:
                    least = max(
                        need["bytes"] / peaks[device]["hbm_bytes_per_s"],
                        need["flops"] / peaks[device]["bf16_flops_per_s"])
                    line[f"gated_delta_{kind}_roofline_pct"] = round(
                        100 * least * 1e3 / ms, 2)
        emit(line)

    if args.conv:
        c, taps_n = args.conv_channels, 4
        ks = jax.random.split(jax.random.PRNGKey(7), 3)
        x = jax.random.normal(ks[0], (batch, length, c), jnp.bfloat16)
        taps = 0.5 * jax.random.normal(ks[1], (taps_n, c))
        dy = jax.random.normal(ks[2], x.shape, jnp.bfloat16)
        kernels = "jnp" if args.impl == "scan" else args.impl

        def pair(impl):
            fn = lambda x, taps: conv.causal_conv(x, taps, jax.nn.silu,
                                                  impl=impl)

            @jax.jit
            def both(x, taps, dy):
                y, pull = jax.vjp(fn, x, taps)
                return (y, *pull(dy))
            return jax.jit(fn), both

        if args.check:
            # the kernels against one position a step in float32, on the
            # chip: what interpret mode cannot show of the pipeline's writes
            def loop(x, taps):
                def step(held, x_t):
                    held = jnp.concatenate([held[1:], x_t[None]])
                    return held, jax.nn.silu(
                        (taps[:, None] * held).sum(0)).astype(x.dtype)
                held = jnp.zeros((taps_n,) + x[:, 0].shape, f32)
                return jnp.moveaxis(jax.lax.scan(
                    step, held, jnp.moveaxis(x, 1, 0).astype(f32))[1], 0, 1)

            @jax.jit
            def wanted(x, taps, dy):
                y, pull = jax.vjp(loop, x, taps)
                return (y, *pull(dy))

            line = {"conv": "causal_conv + silu against the loop",
                    "batch": batch, "length": length, "channels": c,
                    "taps": taps_n, "device": device}
            for dtype in (jnp.float32, jnp.bfloat16):
                xs = (x.astype(dtype), taps, dy.astype(dtype))
                want = wanted(*xs)
                for impl in dict.fromkeys((kernels, "jnp")):
                    got = pair(impl)[1](*xs)
                    line[f"{jnp.dtype(dtype).name}_{impl}"] = {
                        n: off(a, b) for n, a, b in zip(
                            ("y", "dx", "dtaps"), got, want)}
                    del got
                del want
            emit(line)

        cells = batch * length * c
        for impl in dict.fromkeys(("jnp", kernels)):
            fwd, both = pair(impl)
            line = {"conv": "causal_conv + silu", "impl": impl,
                    "batch": batch, "length": length, "channels": c,
                    "taps": taps_n, "device": device,
                    "fwd_ms": timed(fwd, x, taps),
                    "fwd_bwd_ms": timed(both, x, taps, dy),
                    # x in and y out; x and dy in, dx out
                    "fwd_floor_ms": round(2 * cells * 2 / 819e9 * 1e3, 3),
                    "fwd_bwd_floor_ms": round(5 * cells * 2 / 819e9 * 1e3, 3)}
            by_op = {}
            for name, ns in device_ops(both, x, taps, dy):
                op = xplane.short_name(name)
                by_op[op] = by_op.get(op, 0) + ns / 3e6
                kernel = CONV_KERNEL.search(name)
                if kernel:
                    kind = kernel.group(1)
                    key = f"causal_conv_{kind}_kernel_ms"
                    line[key] = round(line.get(key, 0) + ns / 3e6, 3)
                    line[f"causal_conv_{kind}_needed_bytes"] = \
                        conv.causal_needed_bytes(batch * length, c,
                                                 taps_n, 2, kind == "bwd")
            if device in peaks:
                for kind in ("fwd", "bwd"):
                    if f"causal_conv_{kind}_kernel_ms" in line:
                        line[f"causal_conv_{kind}_roofline_pct"] = round(
                            100 * line[f"causal_conv_{kind}_needed_bytes"]
                            / peaks[device]["hbm_bytes_per_s"] * 1e3
                            / line[f"causal_conv_{kind}_kernel_ms"], 2)
            line["fwd_bwd_device_ms"] = round(sum(by_op.values()), 3)
            line["ops_ms"] = {op: round(ms, 3) for op, ms in sorted(
                by_op.items(), key=lambda kv: -kv[1])[:10]}
            emit(line)


if __name__ == "__main__":
    main()
