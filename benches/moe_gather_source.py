"""What a gather of tokens x k rows back to the tokens costs by its source:
the source's length, the rows of it that are touched, and the column blocks
it is cut into. ``ops.moe._sum_of_pairs`` chooses its column blocks, and
``ops.moe._gathered`` the lengths it cuts its source to
(``_gather_sources``), from this table. Since PR 53 that is the layer's
path only where the kernel ``to_tokens`` does not run (under a mesh, at
shapes ``ops.moe._token_blocks`` refuses; off a TPU the table says nothing);
``benches/moe_row_buffer.py`` times both. Run on the chip; prints one JSON
line a case, ms a call.

    python benches/moe_gather_source.py

The sizes are the cell ``joyai-llm-flash.step-8k``'s: 16,384 tokens, 8 pairs
a token, rows of 2,048 in bfloat16. ``present`` pairs at random places are
``mine`` and lie, in a random order, in the source's first ``present`` rows;
the others point at the source's last row and are masked. ``sliced`` cuts
the source out of a buffer tokens x k long inside the compiled function, as
the layer does.
"""

import argparse
import json
import time


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--tokens", type=int, default=16384)
    parser.add_argument("--k", type=int, default=8)
    parser.add_argument("--d", type=int, default=2048)
    parser.add_argument("--present",
                        default="8200,25000,45000,65536,90000,131072")
    parser.add_argument("--reps", type=int, default=20)
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    T, k, d = args.tokens, args.k, args.d
    pairs = T * k

    def timed(fn, *xs):
        jax.block_until_ready(fn(*xs))
        start = time.perf_counter()
        for _ in range(args.reps):
            out = fn(*xs)
        jax.block_until_ready(out)
        return round((time.perf_counter() - start) / args.reps * 1e3, 3)

    def gather_sum(length, parts):
        @jax.jit
        def fn(buffer, at, mine, weights):
            sums = []
            for block in jnp.split(buffer[:length], parts, axis=1):
                rows = block.at[jnp.minimum(at, length - 1)].get(
                    mode="promise_in_bounds").reshape(T, k, -1)
                rows = jnp.where(mine[..., None], rows.astype(jnp.float32),
                                 0.0)
                sums.append((rows * weights[..., None]).sum(axis=1))
            return jnp.concatenate(sums, axis=1).astype(buffer.dtype)
        return fn

    weights = jax.random.uniform(jax.random.PRNGKey(5), (T, k))
    whole = jax.random.normal(jax.random.PRNGKey(0), (pairs, d), jnp.bfloat16)
    eighth = pairs // 8
    for present in (int(n) for n in args.present.split(",")):
        rng = np.random.default_rng(present)
        mine = np.zeros(pairs, bool)
        mine[rng.permutation(pairs)[:present]] = True
        place = np.full(pairs, pairs - 1, np.int64)
        place[mine] = rng.permutation(present)
        at = jnp.asarray(place.astype(np.int32))
        mask = jnp.asarray(mine.reshape(T, k))
        rung = -(-present // eighth) * eighth
        cases = [(pairs, False)] + [
            (rung, sliced) for sliced in (False, True) if rung < pairs]
        for length, sliced in cases:
            buffer = whole if sliced or length == pairs else whole[:length]
            line = {"present": present, "source_rows": length,
                    "sliced": sliced,
                    "device": jax.devices()[0].device_kind}
            for parts in (1, 2, 4, 8):
                line[f"parts{parts}_ms"] = timed(
                    gather_sum(length, parts), buffer, at, mask, weights)
            print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
