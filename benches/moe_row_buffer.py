"""Forward plus backward time of the held experts' layer alone
(``ops.moe.held_expert_ffn``) by the pairs present, beside the one-length
computation it replaced (PR 31's function, kept here verbatim as
``one_length``) and beside itself with the gathers back to the tokens that
the kernel ``to_tokens`` replaced on a TPU (PR 53; ``gathers``: the path
``ops.moe._gathered``, which stays off a TPU and under a mesh): the table in
``held_expert_ffn``'s docstring. And the way back to the tokens alone,
``to_tokens_ms`` of the kernel (``ops.moe._placed``, with and without
weights) and of the gathers, with the share of the rows the kernel read that
held a pair. Run on the chip; prints two JSON lines a count of pairs present:
the way back alone (``what`` ``to_tokens``), then the layer (``layer``).

    python benches/moe_row_buffer.py --present 8200,45000,131072
    python benches/moe_row_buffer.py --tokens 32768 --k 4 --held 8 --of 4 \
        --width 1792 --present 32768,131072 --check 1

The default sizes are the cell ``joyai-llm-flash.step-8k``'s: 16,384 tokens,
8 experts a token, rows of 2,048, experts 768 wide, 16 of 256 held; bfloat16
rows (``--dtype``) on float32 weights. ``lfm2-8b-a1b.step-8k``'s are 32,768 x
4, 8 of 32, 1,792 wide (above); ``trinity-mini.step-16k``'s 16,384 x 8, 8 of
128, 1,024 wide: ``--tokens 16384 --k 8 --held 8 --of 16 --width 1024``.
``--present`` pairs, at random places, fall on held experts, the rest on
absent ones; a token's k experts differ, as ``topk_routing``'s do.
``--check 1`` holds the kernel to the gathers on the chip, NaN planted in
every row past the count: the way back alone, then the layer's result and
four gradients. ``--blocks 256,512`` times the kernel at other blocks of
tokens than ``ops.moe._token_blocks`` chooses.
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


def routing(np, rng, T, k, held, of, present):
    """(T, k) experts, ``present`` pairs at random places on experts 0 ..
    held - 1 and the rest on the others, no expert twice in a row."""
    on_held = np.zeros(T * k, bool)
    on_held[rng.permutation(T * k)[:present]] = True
    mine = np.sort(on_held.reshape(T, k), axis=1)[:, ::-1]  # held first
    assert k <= held and (of > 1 or present == T * k), (k, held, of)
    ours = np.argsort(rng.random((T, held)), axis=1)[:, :k]
    others = held + np.argsort(rng.random((T, max(held * (of - 1), k))),
                               axis=1)[:, :k]
    return rng.permuted(np.where(mine, ours, others), axis=1).astype(np.int32)


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
    parser.add_argument("--paths", default="one_length,gathers,kernel")
    parser.add_argument("--blocks", default="")
    parser.add_argument("--check", type=int, default=0)
    parser.add_argument("--dtype", default="bfloat16",
                        choices=["bfloat16", "float32"])
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import moe

    T, k, d, held = args.tokens, args.k, args.d, args.held
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    dtype = jnp.dtype(args.dtype)
    x = jax.random.normal(keys[0], (T, d), dtype)
    g = jax.random.normal(keys[1], (T, d), dtype)
    weights = jax.random.uniform(keys[2], (T, k), jnp.float32, 0.1, 0.5)
    wi = jax.random.normal(keys[3], (held, d, 2 * args.width)) * 0.02
    wo = jax.random.normal(keys[4], (held, args.width, d)) * 0.02
    sorted_rows = jax.random.normal(keys[5], (T * k, d), dtype)

    def timed(fn, *xs):
        jax.block_until_ready(fn(*xs))
        start = time.perf_counter()
        for _ in range(args.reps):
            out = fn(*xs)
        jax.block_until_ready(out)
        return round((time.perf_counter() - start) / args.reps * 1e3, 3)

    def both_ways(ffn):
        def loss(x, weights, wi, wo, experts):
            y, _ = ffn(x, experts, weights, wi, wo, index=0, of=args.of)
            return (y.astype(jnp.float32) * g).sum(), y
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3), has_aux=True))

    def close(got, want):
        """Equal to one rounding of the rows' type (a token's pairs are
        summed in another order), and finite."""
        got, want = (np.asarray(a, np.float32) for a in (got, want))
        ulp = 2.0**-7 if dtype == jnp.bfloat16 else 2.0**-20
        return bool(np.isfinite(got).all() and (
            np.abs(got - want) <= ulp * np.abs(want) + 1e-6).all())

    chosen = moe._token_blocks
    off_chip = jax.default_backend() != "tpu"     # a rehearsal on the CPU
    rungs = moe.row_buffer_rungs(T * k)
    gathers = jax.jit(lambda rows, plan, w: moe._gathered(
        rows, plan, w).astype(rows.dtype))
    both = (("", None), ("_weighted", weights))
    lines, routings, checks = {}, {}, []
    for present in (int(n) for n in args.present.split(",")):
        experts = routings[present] = jnp.asarray(routing(
            np, np.random.default_rng(present), T, k, held, args.of, present))
        plan = jax.jit(lambda e: moe._plan(e, held, 0))(experts)
        rows = sorted_rows.at[present:].set(jnp.nan)
        line = {
            "present": present,
            "rows_buffered": rungs[int(moe.row_buffer_rung(present, T * k))],
            "device": jax.devices()[0].device_kind}
        lines[present] = {"what": "layer", **line}
        block, chunk = chosen(rows, plan)
        for name, w in both:
            line["to_tokens_ms_gathers" + name] = timed(gathers, rows, plan, w)
        for b in [block] + [int(b) for b in args.blocks.split(",") if b]:
            # the rows the kernel's chunks hold, as ``_to_tokens_kernel``
            # lays them: from the whole tile at or before a range's start
            starts, counts = (
                np.asarray(a) for a in moe._block_ranges(plan, b))
            tile = 32 // rows.dtype.itemsize
            first = starts // tile * tile
            read = chunk * (-(-(starts + counts - first) // chunk)
                            )[counts > 0].sum()
            at = "" if b == block else "_block%d" % b
            line["rows_read" + at] = int(read)
            line["rows_read_held_a_pair_pct" + at] = round(
                100.0 * present / max(read, 1), 1)
            kernel = jax.jit(lambda rows, plan, w: moe._placed(
                rows, plan, w, b, chunk, interpret=off_chip))
            for name, w in both:
                line["to_tokens_ms_kernel" + name + at] = timed(
                    kernel, rows, plan, w)
                if args.check:
                    checks.append(close(kernel(rows, plan, w),
                                        gathers(rows, plan, w)))
                    line["to_tokens_close" + name + at] = checks[-1]
        line["block"], line["chunk"] = block, chunk
        print(json.dumps(dict(line, what="to_tokens")), flush=True)

    layers = {"one_length": one_length(jax, jnp),
              "gathers": moe.held_expert_ffn, "kernel": moe.held_expert_ffn}
    results = {}
    for name in args.paths.split(","):
        # the layer's passes are jitted by themselves: another form, another
        # trace
        moe._token_blocks = (lambda rows, plan: None) if name == "gathers" \
            else chosen
        jax.clear_caches()
        fn = both_ways(layers[name])
        for present, line in lines.items():
            operands = (x, weights, wi, wo, routings[present])
            line[name + "_fwd_bwd_ms"] = timed(fn, *operands)
            if args.check:
                results[name, present] = jax.tree.map(np.asarray, fn(*operands))
    moe._token_blocks = chosen
    for present, line in lines.items():
        if ("kernel", present) in results and ("gathers", present) in results:
            got, want = (jax.tree.leaves(results[name, present])
                         for name in ("kernel", "gathers"))
            line["layer_close"] = [close(a, b) for a, b in zip(got, want)]
            checks.extend(line["layer_close"])
        print(json.dumps(line), flush=True)
    if not all(checks):
        sys.exit(1)


if __name__ == "__main__":
    main()
