"""The gated, grouped RMSNorm between a Mamba-2 block's scan and its
out-projection: ``ops/norm.py``'s kernel pair alone beside the form XLA makes
of ``GroupRMSNorm``'s arithmetic under ``jax.vjp`` (PR 64, step 0b).

    chiprun -- python benches/gated_norm.py --check 1

prints one JSON line a shape: device time a call from a trace of ``--reps``
calls (every operation of the call, so XLA's relayouts count), the bytes'
floor at 819 GB/s (``norm.needed_bytes``) and the share of it, for the
forward kernel, the backward kernel and for ``jnp`` (the twin, forward; its
``vjp`` from kept ``y``, ``z``, ``scale``, backward), then forward +
backward and ``block_ms``, what a recomputed block pays: forward twice and
backward once. ``--check 1`` holds the kernels to the twin and its ``vjp``
on the chip's own arithmetic. ``--sweep 1`` walks the blocks' bytes and the
rows a pass. Off a TPU it exits 1 unless ``--pallas_interpret 1``: a
rehearsal of the bench's code whose lines say ``rehearsal`` and hold wall
times only.

Read on a v5e (my chip run, PR 64, ``chiprun_out/pr64/step0b.jsonl``;
bfloat16, ms a call on the device, the share of the bytes' floor beside it):

    shape (B, T, W, groups)   kernel fwd    kernel bwd     jnp fwd       jnp bwd
    2 x 8,192 x 4,096, 8      0.589 (84%)   1.005 (82%)    4.177 (12%)   7.940 (10%)
    1 x 16,384 x 4,096, 8     0.589 (83%)   1.004 (82%)    4.170 (12%)   7.935 (10%)
    2 x 8,192 x 4,096, 1      0.597 (82%)   1.056 (78%)    0.952 (52%)   1.924 (43%)

Forward + backward 1.59 ms in the kernels against 12.12 in ``jnp`` (8 groups;
1.65 against 2.88 at one group, where the groups' view is the array's own and
XLA copies nothing); a recomputed block, forward twice and backward once,
2.18 ms against 16.29: step 0's gate (under 4 where ``jnp`` reads over 9)
holds. Alone the ``jnp`` form pays more than in the step (12.6 ms a block
there, PERF.md section 6, PR 64), where XLA fuses its ends into the
projections. ``--check 1``: the forward equal to the twin's to the bit at all
three, dy within 0.016-0.031 of a scale of 13-14 and dz within 0.031-0.063 of
25 (one bfloat16 step), the scale's gradient within 0.00025 of 453.

What the constants rest on (``--sweep 1``, forward / backward ms at 2 x 8,192
x 4,096 in 8 groups; a block's bytes are one operand's, forward / backward):
blocks of 0.5 / 0.25 MiB 0.624 / 1.046, of 1 / 0.5 MiB 0.600 / 1.014, of 2 / 1
MiB 0.589 / 1.005 (256 and 128 tokens a grid step: ``_FWD_BLOCK_BYTES``,
``_BWD_BLOCK_BYTES``), of 4 / 2 MiB VMEM exhausted both ways (three and five
arrays, each buffered twice, in the compiler's own 16 MiB); rows a pass 16
0.606 / 1.005, 32 0.589 / 1.005, 64 0.588 / 1.004, 128 0.589 / 1.005
(``_ROWS_A_PASS`` 32: at one group of 4,096 lanes the backward runs out of
VMEM from 64 rows a pass, a dozen float32 arrays of a pass's rows of one
group). A group's mean square is a sum over its lane tiles and one reduction
along the lanes; at 82-84% of the bytes' floor the product with an indicator
on the MXU was not tried.
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
# (batch, tokens, width, groups)
SHAPES = {"nemotron": (2, 8192, 4096, 8), "one_seq": (1, 16384, 4096, 8),
          "one_group": (2, 8192, 4096, 1)}
EPS = 1e-5


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--shapes", default=",".join(SHAPES))
    parser.add_argument("--reps", type=int, default=5)
    parser.add_argument("--check", type=int, default=0)
    parser.add_argument("--sweep", type=int, default=0)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--pallas_interpret", type=int, default=0,
                        help="1: a rehearsal on the CPU (no device time)")
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import norm

    device = {"platform": jax.devices()[0].platform,
              "kind": jax.devices()[0].device_kind}
    interpret = bool(args.pallas_interpret)
    if interpret == (jax.default_backend() == "tpu"):
        sys.exit(f"benches/gated_norm.py reads device times on a TPU; this "
                 f"is {device}: --pallas_interpret 1 rehearses it off one, "
                 "and only there")
    ms = "wall_ms" if interpret else "ms"
    bf16, f32 = jnp.bfloat16, jnp.float32

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

    for name in args.shapes.split(","):
        b, seq, width, groups = SHAPES[name]
        if interpret:
            seq = 256
        keys = jax.random.split(jax.random.PRNGKey(args.seed), 4)
        y = (2.0 * jax.random.normal(keys[0], (b, seq, width), f32)
             ).astype(bf16)
        z = jax.random.normal(keys[1], (b, seq, width), f32).astype(bf16)
        scale = 1 + 0.1 * jax.random.normal(keys[2], (width,), f32)
        do = jax.random.normal(keys[3], (b, seq, width), f32).astype(bf16)
        twin = functools.partial(norm.gated_group_rms_norm_jnp,
                                 groups=groups, eps=EPS)
        kernel = dict(groups=groups, eps=EPS, interpret=interpret)
        reads = {
            "kernel_fwd": (functools.partial(norm.group_norm_fwd, **kernel),
                           (y, z, scale)),
            "kernel_bwd": (functools.partial(norm.group_norm_bwd, **kernel),
                           (y, z, scale, do)),
            "jnp_fwd": (jax.jit(twin), (y, z, scale)),
            "jnp_bwd": (jax.jit(lambda y, z, scale, do: jax.vjp(
                twin, y, z, scale)[1](do)), (y, z, scale, do))}
        line = {"shape": name, "batch": b, "tokens": seq, "width": width,
                "groups": groups, "device": device}
        if interpret:
            line["rehearsal"] = True
        for who, (fn, xs) in reads.items():
            line[f"{who}_{ms}"] = timed(fn, *xs)
            if not interpret:
                floor = norm.needed_bytes(
                    b * seq, width, 2, who.endswith("bwd")
                ) / HBM_BYTES_PER_S * 1e3
                line[f"{who}_floor_pct"] = round(
                    100 * floor / line[f"{who}_ms"], 1)
        for who in ("kernel", "jnp"):
            fwd, bwd = line[f"{who}_fwd_{ms}"], line[f"{who}_bwd_{ms}"]
            line[f"{who}_fwd_bwd_{ms}"] = round(fwd + bwd, 4)
            line[f"{who}_block_{ms}"] = round(2 * fwd + bwd, 4)
        if args.check:
            want, vjp = jax.vjp(twin, y, z, scale)
            got = reads["kernel_fwd"][0](y, z, scale)
            line["check_fwd_max_abs_err"] = float(jnp.abs(
                got.astype(f32) - want.astype(f32)).max())
            assert line["check_fwd_max_abs_err"] <= 0.04, line
            grads = reads["kernel_bwd"][0](y, z, scale, do)
            for what, a, e in zip(("dy", "dz", "dscale"), grads, vjp(do)):
                err = float(jnp.abs(a.astype(f32) - e.astype(f32)).max())
                size = float(jnp.abs(e.astype(f32)).max())
                line[f"check_{what}_max_abs_err"] = err
                line[f"check_{what}_scale"] = size
                assert err <= 0.02 * size + 1e-3, line
        print(json.dumps(line), flush=True)
        if args.sweep and not interpret:
            chosen = (norm._FWD_BLOCK_BYTES, norm._BWD_BLOCK_BYTES,
                      norm._ROWS_A_PASS)
            mib = 2**20
            for fwd_bytes, bwd_bytes, rows in (
                    (mib, mib // 2, 32), (2 * mib, mib, 16),
                    (2 * mib, mib, 64), (2 * mib, mib, 128),
                    (4 * mib, 2 * mib, 32), (mib // 2, mib // 4, 32)):
                norm._FWD_BLOCK_BYTES = fwd_bytes
                norm._BWD_BLOCK_BYTES = bwd_bytes
                norm._ROWS_A_PASS = rows
                jax.clear_caches()
                swept = {"shape": name, "fwd_block_bytes": fwd_bytes,
                         "bwd_block_bytes": bwd_bytes, "rows_a_pass": rows}
                for who in ("kernel_fwd", "kernel_bwd"):
                    fn, xs = reads[who]
                    try:
                        swept[f"{who}_ms"] = timed(fn, *xs)
                    except Exception as e:   # a block VMEM refuses
                        swept[f"{who}_ms"] = str(e).splitlines()[0][:120]
                print(json.dumps(swept), flush=True)
            (norm._FWD_BLOCK_BYTES, norm._BWD_BLOCK_BYTES,
             norm._ROWS_A_PASS) = chosen
            jax.clear_caches()


if __name__ == "__main__":
    main()
