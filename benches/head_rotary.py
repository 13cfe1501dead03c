"""The heads' norm and the rotation between a layer's q / k projections and
its flash kernels: ``ops/rotary.py``'s kernel pair alone beside the form XLA
made of ``rotate_halves(RMSNorm(x))`` under ``jax.grad`` (PR 63, step 0b).

    chiprun -- python benches/head_rotary.py --check 1

prints one JSON line a shape, operand (q of 32 heads, k of 4) and rotation:
device time a call from a trace of ``--reps`` calls (every operation of the
call, so XLA's relayouts count), the bytes' floor at 819 GB/s
(``rotary.needed_bytes``) and the share of it, for the forward kernel, the
backward kernel (q's from the flash backward's float32 [B x H, 128, T] sum,
turned in VMEM; k's from [B, T, G x 128]) and for ``jnp``: forward from the
projection's [B, T, H x 128] to the flash kernels' [B x H, T, 128], backward
from the float32 dQ^T sum (rounded and turned as ``_flash_pallas_bwd_kernel``
did) or from dK to the projection's gradient and the scale's. ``layer_ms``
is what a recomputed layer pays: q and k forward twice and backward once.
``--check 1`` holds the kernels to the twin ``rotary.head_rotary`` and its
``vjp`` on the chip's own arithmetic. ``--sweep 1`` walks the block's tokens,
heads and the rows a pass. Off a TPU it exits 1 unless ``--pallas_interpret
1``: a rehearsal of the bench's code whose lines say ``rehearsal`` and hold
wall times only.

Read on a v5e (my chip runs, PR 63; bfloat16, ms a call on the device):

    shape (B, T)   operand  rotated  kernel fwd   kernel bwd    jnp fwd  jnp bwd
    2 x 8,192      q (32)   yes      0.453 (72%)  0.832 (79%)   4.81     10.59
    2 x 8,192      k (4)    yes      0.065 (63%)  0.091 (68%)   0.30     0.73
    2 x 8,192      q (32)   no       0.408 (80%)  0.768 (85%)   2.82     5.53
    2 x 8,192      k (4)    no       0.052 (79%)  0.077 (80%)   0.22     0.49
    1 x 16,384     q (32)   yes      0.459 (71%)  0.825 (79%)   3.37     9.83
    1 x 16,384     k (4)    yes      0.083 (49%)  0.111 (56%)   0.08     0.75
    1 x 16,384     q (32)   no       0.408 (80%)  0.762 (86%)   2.39     4.64
    1 x 16,384     k (4)    no       0.052 (78%)  0.076 (81%)   0.07     0.49

(the share is of the bytes' floor; the ``jnp`` form stands at 6-19% of it, its
k forward at one sequence apart). A layer, q and k forward twice and backward
once: 1.96 ms in the kernels against 21.54 (2 x 8,192, rotated), 1.76 against
12.09 (not rotated), 2.02 against 17.49 and 1.76 against 10.04 at 1 x 16,384:
step 0's gate (under 4 where ``jnp`` reads over 10) holds at all four. Alone,
the ``jnp`` form pays more than in a step (59 ms in four layers there), where
XLA fuses its ends into the projections. ``--check 1``: the forward equal to
the twin's to the bit at all eight, dx within 0.016 of a scale of 4.3-6.9
(one bfloat16 step), the scale's gradient within 0.002 of 720-2,630.

What the constants rest on (``--sweep 1``, the q operand rotated at 2 x 8,192,
forward / backward ms; ``chiprun_out/pr63/step0b.jsonl``): rows a pass 128
0.779 / 1.371, 256 0.504 / 1.039, 512 0.451 / 0.825 (a pass joins the
tables' halves once for its four heads: ``_ROWS_A_PASS`` 512); tokens a step
512 0.532 / 1.045, 1,024 0.504 / 1.039, 2,048 0.504 / VMEM exhausted;
heads a step 2 0.611 / 1.186, 4 0.504 / 1.039, 8 0.454 / VMEM exhausted (at
512 tokens 0.466 / 1.005): 1,024 tokens of 4 heads, the largest block the
backward's float32 operand leaves room for in the compiler's own 16 MiB.
"""

import argparse
import functools
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

HBM_BYTES_PER_S = 819e9      # one v5e chip (perfbench/peaks.json)
# (batch, tokens, query heads, key-value heads)
SHAPES = {"mellum2": (2, 8192, 32, 4), "window": (1, 16384, 32, 4)}


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--shapes", default="mellum2,window")
    parser.add_argument("--reps", type=int, default=5)
    parser.add_argument("--check", type=int, default=0)
    parser.add_argument("--sweep", type=int, default=0)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--pallas_interpret", type=int, default=0,
                        help="1: a rehearsal on the CPU (no device time)")
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp

    from ray_tpu.models.afmoe import rotate_halves
    from ray_tpu.models.llama import rope_frequencies
    from ray_tpu.ops import rotary

    device = {"platform": jax.devices()[0].platform,
              "kind": jax.devices()[0].device_kind}
    interpret = bool(args.pallas_interpret)
    if interpret == (jax.default_backend() == "tpu"):
        sys.exit(f"benches/head_rotary.py reads device times on a TPU; this "
                 f"is {device}: --pallas_interpret 1 rehearses it off one, "
                 "and only there")
    ms = "wall_ms" if interpret else "ms"
    bf16, f32, eps = jnp.bfloat16, jnp.float32, 1e-5

    def timed(fn, *xs):
        """ms a call of everything the device ran for it, from a trace of
        ``reps`` calls; a rehearsal: one call's wall time."""
        jax.block_until_ready(fn(*xs))
        if interpret:
            start = time.perf_counter()
            jax.block_until_ready(fn(*xs))
            return round((time.perf_counter() - start) * 1e3, 4)
        from perfbench import xplane

        trace_dir = tempfile.mkdtemp()
        try:
            with jax.profiler.trace(trace_dir):
                for _ in range(args.reps):
                    out = fn(*xs)
                jax.block_until_ready(out)
            ops = xplane.load(xplane.find_xplane(trace_dir)).ops.get(0, ())
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        busy = sum(end - start for name, start, end in ops
                   if not xplane.short_name(name).startswith(
                       xplane._CONTROL_FLOW))
        return round(busy / args.reps / 1e6, 4)

    def parents(b, seq, heads, cos, sin):
        """(forward, backward) as the parent's step had them: the two
        modules' arithmetic (the normed value rounded, widened and rounded
        again), the fold to the flash kernels' layout and, backward, the
        rounding and turn of the float32 dQ^T sum, all XLA's."""
        def forward(x, scale):
            xf = x.reshape(b, seq, heads, 128).astype(f32)
            n = (xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                                    + eps) * scale).astype(x.dtype)
            if cos is not None:
                n = rotate_halves(n, cos, sin)
            return n.transpose(0, 2, 1, 3).reshape(b * heads, seq, 128)

        def backward(g, x, scale, turned):
            if turned:    # ``jnp.swapaxes(dq_t.astype(q.dtype), 1, 2)``
                g = jnp.swapaxes(g.astype(x.dtype), 1, 2)
            else:         # dK in the model's array: folded as k is
                g = g.reshape(b, seq, heads, 128).transpose(
                    0, 2, 1, 3).reshape(b * heads, seq, 128)
            return jax.vjp(forward, x, scale)[1](g)

        return forward, backward

    for name in args.shapes.split(","):
        b, seq, q_heads, kv_heads = SHAPES[name]
        if interpret:
            seq = 256
        for rotated in (1, 0):
            table = rope_frequencies(128, jnp.arange(seq)[None], 10000.0)
            cos, sin = table if rotated else (None, None)
            flat = rotary.tables(*table) if rotated else (None, None)
            layer = {"kernel": 0.0, "jnp": 0.0}
            for operand, heads in (("q", q_heads), ("k", kv_heads)):
                turned = operand == "q"
                keys = jax.random.split(jax.random.PRNGKey(args.seed), 3)
                x = jax.random.normal(keys[0], (b, seq, heads * 128), bf16)
                scale = 1 + 0.1 * jax.random.normal(keys[1], (128,), f32)
                g = (jax.random.normal(keys[2], (b * heads, 128, seq), f32)
                     if turned else
                     jax.random.normal(keys[2], x.shape, bf16))
                fwd = jax.jit(functools.partial(
                    rotary.head_rotary_fwd, heads=heads, eps=eps,
                    interpret=interpret))
                bwd = jax.jit(functools.partial(
                    rotary.head_rotary_bwd, heads=heads, eps=eps,
                    turned=turned, interpret=interpret))
                forward, backward = parents(b, seq, heads, cos, sin)
                line = {"shape": name, "operand": operand, "heads": heads,
                        "batch": b, "tokens": seq, "rotated": rotated,
                        "device": device}
                if interpret:
                    line["rehearsal"] = True
                reads = {
                    "kernel_fwd": (fwd, (x, scale, *flat)),
                    "kernel_bwd": (bwd, (g, x, scale, *flat)),
                    "jnp_fwd": (jax.jit(forward), (x, scale)),
                    "jnp_bwd": (jax.jit(functools.partial(
                        backward, turned=turned)), (g, x, scale))}
                for who, (fn, xs) in reads.items():
                    line[f"{who}_{ms}"] = timed(fn, *xs)
                    if interpret:
                        continue
                    floor = rotary.needed_bytes(
                        b * seq, heads, 2, backward=who.endswith("bwd"),
                        turned=turned) / HBM_BYTES_PER_S * 1e3
                    line[f"{who}_floor_pct"] = round(
                        100 * floor / line[f"{who}_ms"], 1)
                for who in layer:
                    layer[who] += (2 * line[f"{who}_fwd_{ms}"]
                                   + line[f"{who}_bwd_{ms}"])
                if args.check:
                    as_model = lambda y: y.reshape(
                        b, heads, seq, 128).transpose(0, 2, 1, 3)
                    want, vjp = jax.vjp(
                        lambda x, scale: rotary.head_rotary(
                            x.reshape(b, seq, heads, 128), scale, cos, sin,
                            eps=eps), x, scale)
                    got = as_model(fwd(x, scale, *flat))
                    line["check_fwd_max_abs_err"] = float(jnp.abs(
                        got.astype(f32) - want.astype(f32)).max())
                    g_model = (as_model(jnp.swapaxes(g, 1, 2)) if turned
                               else g.reshape(want.shape).astype(f32))
                    (want_dx, want_ds), (dx, ds) = vjp(
                        g_model.astype(bf16)), bwd(g, x, scale, *flat)
                    if turned:   # the kernel reads the unrounded sum
                        want_dx, want_ds = jax.vjp(
                            lambda x, scale: rotary.head_rotary(
                                x.reshape(b, seq, heads, 128).astype(f32),
                                scale, cos, sin, eps=eps), x, scale)[1](
                                    g_model)
                    err_dx = float(jnp.abs(
                        dx.astype(f32) - want_dx.astype(f32)).max())
                    err_ds = float(jnp.abs(ds - want_ds).max())
                    scale_dx = float(jnp.abs(want_dx.astype(f32)).max())
                    scale_ds = float(jnp.abs(want_ds).max())
                    line.update(check_dx_max_abs_err=err_dx,
                                check_dx_scale=scale_dx,
                                check_dscale_max_abs_err=err_ds,
                                check_dscale_scale=scale_ds)
                    assert line["check_fwd_max_abs_err"] <= 0.04, line
                    assert err_dx <= 0.02 * scale_dx, line
                    assert err_ds <= 0.01 * scale_ds + 1e-3, line
                print(json.dumps(line), flush=True)
                if args.sweep and not interpret:
                    chosen = (rotary._BLOCK_TOKENS, rotary._HEADS_A_STEP,
                              rotary._ROWS_A_PASS)
                    for tokens, a_step, rows in (
                            (512, 4, 256), (1024, 4, 128), (1024, 4, 512),
                            (2048, 4, 256), (1024, 2, 256), (1024, 8, 256),
                            (2048, 2, 256), (512, 8, 256)):
                        if a_step > heads:
                            continue
                        rotary._BLOCK_TOKENS = tokens
                        rotary._HEADS_A_STEP = a_step
                        rotary._ROWS_A_PASS = rows
                        jax.clear_caches()
                        swept = {"shape": name, "operand": operand,
                                 "rotated": rotated, "tokens_a_step": tokens,
                                 "heads_a_step": a_step, "rows_a_pass": rows}
                        for who in ("kernel_fwd", "kernel_bwd"):
                            fn, xs = reads[who]
                            try:
                                swept[f"{who}_ms"] = timed(fn, *xs)
                            except Exception as e:   # a block VMEM refuses
                                swept[f"{who}_ms"] = str(e).splitlines()[0][
                                    :120]
                        print(json.dumps(swept), flush=True)
                    (rotary._BLOCK_TOKENS, rotary._HEADS_A_STEP,
                     rotary._ROWS_A_PASS) = chosen
                    jax.clear_caches()
            print(json.dumps({
                "shape": name, "rotated": rotated,
                **{f"layer_{who}_{ms}": round(v, 4)
                   for who, v in layer.items()}}), flush=True)


if __name__ == "__main__":
    main()
