"""Forward plus backward time of causal self-attention by path, at the head
widths and lengths given: what ``ops.attention.auto_attention`` decides
from. Run on the chip; prints one JSON line a shape.

    python benches/flash_widths.py --widths 192x128 --lengths 1024,8192

Tokens a call are held at ``--tokens`` (16,384: the cells' load), heads at
``--heads``. ``xla`` is what ``causal_self_attention(..., "xla")`` runs; it
is skipped where its [B, H, T, T] scores would not fit (past 4096).
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--widths", default="192x128,128x128")
    parser.add_argument("--lengths", default="512,1024,2048,4096,8192")
    parser.add_argument("--tokens", type=int, default=16384)
    parser.add_argument("--heads", type=int, default=32)
    parser.add_argument("--reps", type=int, default=10)
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.attention import causal_self_attention

    def timed(fn, *xs):
        jax.block_until_ready(fn(*xs))
        start = time.perf_counter()
        for _ in range(args.reps):
            out = fn(*xs)
        jax.block_until_ready(out)
        return (time.perf_counter() - start) / args.reps * 1e3

    for width in args.widths.split(","):
        d_qk, d_v = (int(n) for n in width.split("x"))
        for seq in (int(n) for n in args.lengths.split(",")):
            batch = max(1, args.tokens // seq)
            keys = jax.random.split(jax.random.PRNGKey(seq), 3)
            q = jax.random.normal(keys[0], (batch, seq, args.heads, d_qk),
                                  jnp.bfloat16)
            k = jax.random.normal(keys[1], q.shape, jnp.bfloat16)
            v = jax.random.normal(keys[2], (batch, seq, args.heads, d_v),
                                  jnp.bfloat16)
            line = {"d_qk": d_qk, "d_v": d_v, "seq": seq, "batch": batch,
                    "heads": args.heads, "device": jax.devices()[0].device_kind}
            for path in ("flash", "xla"):
                if path == "xla" and seq > 4096:
                    continue

                def loss(q, k, v, path=path):
                    return causal_self_attention(q, k, v, path).astype(
                        jnp.float32).sum()

                fn = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
                try:
                    line[path + "_fwd_bwd_ms"] = round(timed(fn, q, k, v), 3)
                except Exception as e:  # a path that does not fit or lower
                    line[path + "_error"] = str(e)[:200]
            print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
