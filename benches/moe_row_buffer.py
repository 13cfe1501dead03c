"""Forward plus backward time of the held experts' layer alone
(``ops.moe.held_expert_ffn``) by the pairs present, beside the one-length
computation it replaced (PR 31's function, kept here verbatim as
``one_length``): the table in ``held_expert_ffn``'s docstring. Run on the
chip; prints one JSON line a count of pairs present.

    python benches/moe_row_buffer.py --present 8200,45000,131072

The sizes are the cell ``joyai-llm-flash.step-8k``'s: 16,384 tokens, 8
experts a token, rows of 2,048, experts 768 wide, 16 of 256 held; bfloat16
rows on float32 weights. ``--present`` pairs, at random places, fall on
held experts (uniformly over them), the rest on absent ones.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def one_length(jax, jnp):
    """PR 31's ``held_expert_ffn``: one row buffer tokens x k long."""
    import functools

    def take(x, index):
        return x.at[index].get(mode="promise_in_bounds")

    @functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
    def rows_of_sorted_pairs(x, order, inverse, mine, k):
        return take(x, order // k)

    def rows_bwd(k, res, g):
        inverse, mine = res
        pairs = take(g, inverse).reshape(-1, k, g.shape[-1])
        dx = jnp.where(mine[..., None], pairs.astype(jnp.float32),
                       0.0).sum(axis=1)
        return dx.astype(g.dtype), None, None, None

    rows_of_sorted_pairs.defvjp(
        lambda x, order, inverse, mine, k: (take(x, order // k),
                                            (inverse, mine)), rows_bwd)

    @jax.custom_vjp
    def unsort(y, order, inverse):
        return take(y, inverse)

    unsort.defvjp(lambda y, order, inverse: (take(y, inverse), order),
                  lambda order, g: (take(g, order), None, None))

    def ffn(x, experts, weights, wi, wo, *, index, of):
        T, d = x.shape
        k, held = experts.shape[1], wi.shape[0]
        local = experts - index * held
        mine = (local >= 0) & (local < held)
        key = jnp.where(mine, local, held).reshape(-1)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        inverse = jnp.zeros_like(order).at[order].set(
            jnp.arange(T * k, dtype=jnp.int32))
        tokens = jnp.zeros((held + 1,), jnp.int32).at[key].add(1)[:held]
        rows = rows_of_sorted_pairs(x, order, inverse, mine, k)
        hidden = jax.lax.ragged_dot(rows, wi.astype(x.dtype), tokens)
        gate, up = jnp.split(hidden, 2, axis=-1)
        out = jax.lax.ragged_dot(jax.nn.silu(gate) * up, wo.astype(x.dtype),
                                 tokens)
        pairs = unsort(out, order, inverse).reshape(T, k, d)
        pairs = jnp.where(mine[..., None], pairs.astype(jnp.float32), 0.0)
        y = jnp.einsum("tkd,tk->td", pairs, jnp.where(mine, weights, 0.0))
        return y.astype(x.dtype), tokens

    return ffn


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--present",
                        default="8200,16384,25000,45000,65536,131072")
    parser.add_argument("--tokens", type=int, default=16384)
    parser.add_argument("--k", type=int, default=8)
    parser.add_argument("--d", type=int, default=2048)
    parser.add_argument("--width", type=int, default=768)
    parser.add_argument("--held", type=int, default=16)
    parser.add_argument("--of", type=int, default=16)
    parser.add_argument("--reps", type=int, default=10)
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import moe

    T, k, d, held = args.tokens, args.k, args.d, args.held
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(keys[0], (T, d), jnp.bfloat16)
    g = jax.random.normal(keys[1], (T, d), jnp.bfloat16)
    weights = jax.random.uniform(keys[2], (T, k), jnp.float32, 0.1, 0.5)
    wi = jax.random.normal(keys[3], (held, d, 2 * args.width)) * 0.02
    wo = jax.random.normal(keys[4], (held, args.width, d)) * 0.02

    def timed(fn, *xs):
        jax.block_until_ready(fn(*xs))
        start = time.perf_counter()
        for _ in range(args.reps):
            out = fn(*xs)
        jax.block_until_ready(out)
        return (time.perf_counter() - start) / args.reps * 1e3

    def both_ways(ffn):
        def loss(x, weights, wi, wo, experts):
            y, _ = ffn(x, experts, weights, wi, wo, index=0, of=args.of)
            return (y.astype(jnp.float32) * g).sum()
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3)))

    paths = {"one_length": both_ways(one_length(jax, jnp)),
             "rungs": both_ways(moe.held_expert_ffn)}
    rungs = moe.row_buffer_rungs(T * k)
    for present in (int(n) for n in args.present.split(",")):
        rng = np.random.default_rng(present)
        on_held = np.zeros(T * k, bool)
        on_held[rng.permutation(T * k)[:present]] = True
        experts = jnp.asarray(np.where(
            on_held, rng.integers(0, held, T * k),
            rng.integers(held, held * args.of, T * k)
        ).reshape(T, k).astype(np.int32))
        line = {"present": present,
                "rows_buffered": rungs[int(moe.row_buffer_rung(present,
                                                               T * k))],
                "device": jax.devices()[0].device_kind}
        for name, fn in paths.items():
            line[name + "_fwd_bwd_ms"] = round(
                timed(fn, x, weights, wi, wo, experts), 3)
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
