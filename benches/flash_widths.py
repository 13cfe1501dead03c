"""Forward time, and forward plus backward, of causal self-attention by path,
at the head widths and lengths given: what ``ops.attention.auto_attention``
decides from. Run on the chip; prints one JSON line a shape, with the kinds
of grid block a head of the kernel's call has (``grid_block_kinds``:
``looped`` 0 where every block is walked in straight-line code) and, from a
profiler trace of three more calls, the device time of one ``flash_fwd``
and one ``flash_bwd`` alone (``flash_*_kernel_ms``), what the wall time of
forward plus backward holds besides them (``round_kernels_ms``: on the
``xla_copies`` boundary the V^T, K^T, O^T and dQ^T transposes, ``delta`` and
dQ's rounding, until PR 50 the sum of dQ's float32 partials too; on
``model_results``, since PR 55 the boundary past one block of keys at one
width of whole lane tiles, what is left of them: q's, K^T's and V^T's; on
any the bench's own product of the output with its cotangent) and the bytes
of dQ that the backward call writes (``dq_written_bytes``: its first output,
as the traced call declares it; since PR 50 one float32 [B x H, d, T] sum
where a head has several blocks of keys, where there was one such array a
block of keys; on ``model_results`` the kernel's own running sums, which
nothing reads after it).

Every ARGUMENT is in a model's layout, [B, T, H x d] dense, the output's
cotangent among them (the loss is the sum of the output times a seeded [B,
T, H x d_v] array, so that dO is an array of the program as a model's is,
not a constant that XLA folds into a copy), and the gradients come back in
it: what stands round the kernels is what a model pays (PR 51: a bench whose
arguments are lane-padded pays a relayout the model does not).

    python benches/flash_widths.py --widths 192x128 --lengths 1024,8192
    python benches/flash_widths.py --widths 128x128 --lengths 16384 \
        --kv-heads 4 --window 2048 --check 1

Tokens a call are held at ``--tokens`` (16,384: the cells' load), heads at
``--heads``, of which ``--kv-heads`` are keys' and values' (default: as
many), under ``--window`` keys (default: none). ``xla`` is what
``causal_self_attention(..., "xla")`` runs; it is skipped where its
[B, H, T, T] scores would not fit (past 4096). ``--check 1`` also holds the
kernel's output and three gradients (bfloat16 operands) against
``attention_reference`` on the same operands in float32 at matmul precision
highest, one query head at a time, and prints the largest differences
over the largest reference entry: what interpret mode cannot show of the
pipeline's writes.
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
    parser.add_argument("--widths", default="192x128,128x128")
    parser.add_argument("--lengths", default="512,1024,2048,4096,8192")
    parser.add_argument("--tokens", type=int, default=16384)
    parser.add_argument("--heads", type=int, default=32)
    parser.add_argument("--reps", type=int, default=10)
    parser.add_argument("--kv-heads", type=int, default=None)
    parser.add_argument("--window", type=int, default=None)
    parser.add_argument("--check", type=int, default=0)
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import attention
    from ray_tpu.ops.attention import (attention_reference,
                                       causal_self_attention,
                                       grid_block_kinds, heads_a_lane_tile)

    kv_heads = args.kv_heads or args.heads

    def timed(fn, *xs):
        jax.block_until_ready(fn(*xs))
        start = time.perf_counter()
        for _ in range(args.reps):
            out = fn(*xs)
        jax.block_until_ready(out)
        return (time.perf_counter() - start) / args.reps * 1e3

    def kernel_ms(fn, *xs):
        """{"flash_fwd_kernel_ms": ..., "flash_bwd_kernel_ms": ...}: mean
        device time of a call of each kernel, found in a trace as the
        benchmark's ``attn_kernel_ms`` finds them."""
        from perfbench import xplane
        from perfbench.metrics.attn_kernel_ms import KERNEL

        trace_dir = tempfile.mkdtemp()
        try:
            with jax.profiler.trace(trace_dir):
                for _ in range(3):
                    out = fn(*xs)
                jax.block_until_ready(out)
            ops = xplane.load(xplane.find_xplane(trace_dir)).ops.get(0, ())
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        found = {}
        for name, start, end in ops:
            kernel = KERNEL.match(name)
            if kernel:
                found.setdefault(kernel.group(1), []).append(end - start)
        return {f"flash_{k}_kernel_ms": round(sum(ns) / len(ns) / 1e6, 3)
                for k, ns in found.items()}

    def dq_written_bytes(fn, *xs):
        """Bytes of the first output of the traced ``flash_bwd`` call; None
        where the path runs no kernel (off the chip)."""
        found = []

        def walk(jaxpr):
            for eqn in jaxpr.eqns:
                if (eqn.primitive.name == "pallas_call"
                        and eqn.params["name"].startswith("flash_bwd")):
                    dq = eqn.outvars[0].aval
                    found.append(dq.size * dq.dtype.itemsize)
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)

        walk(jax.make_jaxpr(fn)(*xs).jaxpr)
        return found[0] if found else None

    def against_reference(q, k, v):
        """Largest |kernel - reference| over the largest |reference| entry,
        for the output and the gradients of q, k and v under a seeded
        cotangent."""
        w = jax.random.normal(jax.random.PRNGKey(3), (*q.shape[:3],
                                                      v.shape[-1]))
        f32 = lambda x: x.astype(jnp.float32)

        def out_and_grads(fn, q, k, v, w):
            out, vjp = jax.vjp(lambda *x: f32(fn(*x)), q, k, v)
            return (out, *vjp(w))

        kernel = jax.jit(lambda q, k, v, w: out_and_grads(
            lambda *x: causal_self_attention(*x, "flash", args.window),
            q, k, v, w))
        bhsd = lambda t: t.transpose(0, 2, 1, 3)

        @jax.jit
        def plain(q, k, v, w):
            with jax.default_matmul_precision("highest"):
                return out_and_grads(
                    lambda *x: bhsd(attention_reference(
                        *(bhsd(t) for t in x), causal=True,
                        window=args.window)), f32(q), f32(k), f32(v), w)

        got = kernel(q, k, v, w)
        group = args.heads // kv_heads
        want = [[], [], [], []]
        for head in range(args.heads):   # one query head's scores at a time
            one, kv = slice(head, head + 1), slice(head // group,
                                                   head // group + 1)
            parts = plain(q[:, :, one], k[:, :, kv], v[:, :, kv],
                          w[:, :, one])
            for into, part in zip(want, parts):
                into.append(part)
        of_group = lambda parts: [sum(parts[i:i + group])
                                  for i in range(0, args.heads, group)]
        want = [want[0], want[1], of_group(want[2]), of_group(want[3])]
        return {name: round(float(
            jnp.abs(f32(a) - jnp.concatenate(b, axis=2)).max()
            / max(jnp.abs(x).max() for x in b)), 5)
            for name, a, b in zip(("out", "dq", "dk", "dv"), got, want)}

    for width in args.widths.split(","):
        d_qk, d_v = (int(n) for n in width.split("x"))
        for seq in (int(n) for n in args.lengths.split(",")):
            batch = max(1, args.tokens // seq)
            # operands as a model's projection writes them, [B, T, H x d]
            # dense, and shaped [B, T, H, d] inside the timed function: an
            # argument [B, T, H, 64] would itself be padded to the lanes
            keys = jax.random.split(jax.random.PRNGKey(seq), 4)
            q, k, v, w = (
                jax.random.normal(key, (batch, seq, n * d), jnp.bfloat16)
                for key, n, d in zip(
                    keys, (args.heads, kv_heads, kv_heads, args.heads),
                    (d_qk, d_qk, d_v, d_v)))
            shaped = lambda x, n: x.reshape(batch, seq, n, -1)
            # a tree without the rule (a parent's, under this file) has none
            results = getattr(attention, "results_in_model_arrays",
                              lambda *_: False)
            line = {"d_qk": d_qk, "d_v": d_v, "seq": seq, "batch": batch,
                    "heads": args.heads, "kv_heads": kv_heads,
                    "window": args.window,
                    "boundary": (
                        "model_arrays" if heads_a_lane_tile(
                            seq, args.heads, kv_heads, d_qk, d_v)
                        else "model_results" if results(seq, d_qk, d_v)
                        else "xla_copies"),
                    "device": jax.devices()[0].device_kind,
                    "grid_blocks": grid_block_kinds(seq, seq, True,
                                                    window=args.window),
                    "grid_blocks_bwd": grid_block_kinds(
                        seq, seq, True, backward=True, window=args.window)}
            for path in ("flash", "xla"):
                if path == "xla" and seq > 4096:
                    continue

                def loss(q, k, v, w, path=path):
                    out = causal_self_attention(
                        shaped(q, args.heads), shaped(k, kv_heads),
                        shaped(v, kv_heads), path, args.window)
                    return (out.reshape(w.shape).astype(jnp.float32)
                            * w).sum()

                fns = {"_fwd_ms": jax.jit(loss),
                       "_fwd_bwd_ms": jax.jit(jax.grad(loss, argnums=(0, 1, 2)))}
                try:
                    for name, fn in fns.items():
                        line[path + name] = round(timed(fn, q, k, v, w), 3)
                    if path == "flash":
                        kernels = kernel_ms(fns["_fwd_bwd_ms"], q, k, v, w)
                        line.update(kernels, dq_written_bytes=dq_written_bytes(
                            fns["_fwd_bwd_ms"], q, k, v, w))
                        if kernels:
                            line["round_kernels_ms"] = round(
                                line["flash_fwd_bwd_ms"]
                                - sum(kernels.values()), 3)
                except Exception as e:  # a path that does not fit or lower
                    line[path + "_error"] = str(e)[:200]
            if args.check:
                line["against_reference"] = against_reference(
                    shaped(q, args.heads), shaped(k, kv_heads),
                    shaped(v, kv_heads))
            print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
