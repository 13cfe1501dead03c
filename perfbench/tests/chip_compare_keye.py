"""The comparison that decides ``correct`` in a cell of the ``keye`` family,
made over many seeds in one process, with its control: what a builder runs
on the chip to set the limits in the configuration file.

    python3 perfbench/tests/chip_compare_keye.py \
        --config perfbench/configs/keye-vl-2.0-30b-a3b.json \
        --traffic perfbench/traffic/step-16k-img.json --seeds 1,2,3 \
        [--grad 1] [--control 1] [--flips 1] [--load-steps 40]

For each seed: the state from the seed as the worker makes it (the held
experts levelled), the float32 reference over the seeded batch (both terms
of its loss), the program's two terms, one real step, and the differences
the worker would compute (loss; with ``--grad 1`` the gradient's norm and
cosine over the whole tree, the indexer's leaves among them, and the same
over the indexer's leaves alone), with the worker's verdict under the
configuration's limits. With ``--control 1`` the step is made again from
weights kept to 3 bits of mantissa (the nearest precision below bfloat16)
against the reference on the unrounded weights: that has to come out past
a limit. With ``--flips 1``, layer by layer, the share of selected
memberships on which program and reference differ: the (query, key) pairs
that one of them selected and the other did not, over the pairs both sets
hold together (twice a layer's selected pairs), and the queries with any
such pair. With ``--load-steps n`` the tokens each held expert received
before each of n steps on the one batch, by layer, with each step's wall
time. One JSON line a seed.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--config", required=True)
    parser.add_argument("--traffic", required=True)
    parser.add_argument("--seeds", default="0")
    parser.add_argument("--grad", type=int, default=0)
    parser.add_argument("--control", type=int, default=0)
    parser.add_argument("--flips", type=int, default=0)
    parser.add_argument("--load-steps", type=int, default=0)
    parser.add_argument("--index-dtype", default=None)
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from perfbench import compare, traffic as traffic_mod, worker

    with open(os.path.join(ROOT, args.config)) as f:
        model = json.load(f)
    if args.index_dtype:
        model["train"]["index_dtype"] = args.index_dtype
    spec = traffic_mod.load(os.path.join(ROOT, args.traffic))
    family = worker.load_family(ROOT, model)
    reference = worker.load_reference(ROOT, model)
    built = family.build(model, spec, None)
    make_state = jax.jit(built.make_state)
    parts_of = jax.jit(built.loss_with_parts)
    limits, b1 = model["reference"], model["train"]["adam_b1"]
    triples, image = reference.positions(reference.layout(model), spec["seq"])
    text = np.broadcast_to(np.append(~image[1:], True).astype(np.float32),
                           (spec["batch"], spec["seq"]))
    terms_of = reference.make(model, False)
    masks_of = jax.jit(lambda params, ids: built.net.apply(
        {"params": params}, ids, built.extra["position_ids"],
        mutable=["intermediates"])[1]["intermediates"])

    def chop(x):
        if x.ndim < 2:
            return x
        mantissa, exponent = jnp.frexp(x)
        return jnp.ldexp(jnp.round(mantissa * 16) / 16, exponent)

    def indexer_only(tree):
        return [leaf for path, leaf in jax.tree_util.tree_leaves_with_path(
            tree) if "index_" in jax.tree_util.keystr(path)]

    def step_against(params, opt_state, batch, ref_loss, ref_grads):
        _, opt_state, loss = built.step(params, opt_state, batch)
        out = {"loss": float(loss),
               "loss_rel_diff": abs(float(loss) - ref_loss) / abs(ref_loss)}
        ok = out["loss_rel_diff"] <= limits["loss_rel_tol"]
        if ref_grads is not None:
            system = compare.system_gradient(opt_state, b1)
            ns, nr, cos = (float(v) for v in compare.compare_gradients(
                system, ref_grads))
            out.update(grad_norm_rel_diff=abs(ns - nr) / nr, grad_cosine=cos)
            ns, nr, cos = (float(v) for v in compare.compare_gradients(
                indexer_only(system), indexer_only(ref_grads)))
            out.update(indexer_grad_norm=ns, indexer_reference_grad_norm=nr,
                       indexer_grad_cosine=cos)
            if "gradient" in limits["compare"]:
                ok = (ok and out["grad_norm_rel_diff"]
                      <= limits["grad_norm_rel_tol"]
                      and out["grad_cosine"] >= limits["grad_cosine_min"])
        out["within_tolerance"] = bool(ok)
        return out

    def load_of(params, batch):
        load = parts_of(params, batch)[1]["tokens_per_expert"]
        return {"mean": round(float(load.mean()), 1), "max": int(load.max()),
                "min": int(load.min()),
                "rows_present_by_layer": load.sum(axis=1).tolist()}

    def flips_of(params, batch):
        said = masks_of(params, batch["input_ids"])
        mine = [said[f"layers_{i}"]["attn"]["selected"][0]
                for i in range(model["num_hidden_layers"])]
        out = []
        for i, theirs in enumerate(reference.selections(
                params, batch["input_ids"], triples, m=model)):
            ours = jnp.swapaxes(mine[i], 1, 2) != 0      # queries major
            differ = ours != theirs
            both = int(ours.sum(dtype=jnp.int32)) + int(
                theirs.sum(dtype=jnp.int32))
            out.append({
                "layer": i, "selected": int(ours.sum(dtype=jnp.int32)),
                "reference_selected": int(theirs.sum(dtype=jnp.int32)),
                "differ": int(differ.sum(dtype=jnp.int32)),
                "share": float(differ.sum(dtype=jnp.int32)) / both,
                "queries_touched": int(differ.any(axis=-1).sum())})
            mine[i] = None
        return out

    for seed in (int(s) for s in args.seeds.split(",")):
        key = jax.random.PRNGKey(seed % 2**32)
        tokens = traffic_mod.resident_tokens(seed, spec, model["vocab_size"])
        batch = {"input_ids": jax.device_put(tokens[:, :-1]),
                 "labels": jax.device_put(tokens[:, 1:])}
        params, opt_state = make_state(key)
        line = {"seed": seed, "device": jax.devices()[0].device_kind}
        if args.flips:
            line["flips"] = flips_of(params, batch)
        _, (ref_lm, ref_index) = terms_of(
            params, batch["input_ids"], batch["labels"], jax.device_put(text),
            triples) if spec["batch"] == limits["microbatch"] else (0, (0, 0))
        start = time.perf_counter()
        ref_loss, ref_grads = reference.over_microbatches(
            model, params, tokens, limits["microbatch"], bool(args.grad),
            jax.device_put)
        ref_loss = float(ref_loss)
        parts = parts_of(params, batch)[1]
        line.update(reference_loss=ref_loss, reference_lm=float(ref_lm),
                    reference_index=float(ref_index),
                    reference_s=round(time.perf_counter() - start, 1),
                    lm_loss=float(parts["lm_loss"]),
                    index_loss=float(parts["index_loss"]),
                    expert_tokens=load_of(params, batch))
        line.update(step_against(params, opt_state, batch, ref_loss,
                                 ref_grads))
        if args.control:
            params, opt_state = make_state(key)
            line["control"] = step_against(jax.tree.map(chop, params),
                                           opt_state, batch, ref_loss,
                                           ref_grads)
        del ref_grads
        if args.load_steps:
            params, opt_state = make_state(key)
            over, step_ms = [], []
            for _ in range(args.load_steps):
                over.append(load_of(params, batch))
                start = time.perf_counter()
                params, opt_state, loss = built.step(params, opt_state, batch)
                float(loss)  # the fence, as the worker's loop has it
                step_ms.append(round(1e3 * (time.perf_counter() - start), 2))
            line["load_over_steps"] = {
                k: [o[k] for o in over]
                for k in ("mean", "max", "min", "rows_present_by_layer")}
            line["step_ms"] = step_ms
            del params, opt_state
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
