"""The comparison that decides ``correct`` in a cell of the ``nemotron_h``
family, made over many seeds in one process, with its control: what a
builder runs on the chip to set the limits in the configuration file.

    python3 perfbench/tests/chip_compare_nemotron_h.py \
        --config perfbench/configs/nemotron-3-nano-30b-a3b.json \
        --traffic perfbench/traffic/step-8k.json --seeds 1,2,3 [--grad 1] \
        [--control 1] [--load-steps 64] [--out chiprun_out/pr58]

For each seed: the state from the seed as the worker makes it, the float32
reference over the seeded batch, one real step, and the differences the
worker would compute (loss; with ``--grad 1`` the gradient's norm and
cosine, from Adam's first moment), with the worker's verdict under the
configuration's limits. With ``--control 1`` the step is made again from
weights kept to 3 bits of mantissa (what fp8 e4m3 holds: the nearest
precision below bfloat16), against the reference on the unrounded weights:
that has to come out past a limit. Also printed: the tokens each held expert
received on the cell's batch (the auxiliary output of the program's loss),
and with ``--load-steps n`` the same before each of n steps on the one
batch, by layer, with each step's wall time: what the cell's timed steps see
as the routing drifts. One JSON line
a seed, also appended to ``<out>/chip_compare.jsonl``.
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
    parser.add_argument("--load-steps", type=int, default=0)
    parser.add_argument("--out", default=None)
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp

    from perfbench import compare, traffic as traffic_mod, worker

    with open(os.path.join(ROOT, args.config)) as f:
        model = json.load(f)
    spec = traffic_mod.load(os.path.join(ROOT, args.traffic))
    family = worker.load_family(ROOT, model)
    reference = worker.load_reference(ROOT, model)
    built = family.build(model, spec, None)
    make_state = jax.jit(built.make_state)
    parts_of = jax.jit(built.loss_with_parts)
    limits, b1 = model["reference"], model["train"]["adam_b1"]

    def chop(x):
        if x.ndim < 2:
            return x
        mantissa, exponent = jnp.frexp(x)
        return jnp.ldexp(jnp.round(mantissa * 16) / 16, exponent)

    def step_against(params, opt_state, batch, ref_loss, ref_grads):
        _, opt_state, loss = built.step(params, opt_state, batch)
        out = {"loss": float(loss),
               "loss_rel_diff": abs(float(loss) - ref_loss) / abs(ref_loss)}
        ok = out["loss_rel_diff"] <= limits["loss_rel_tol"]
        if ref_grads is not None:
            ns, nr, cos = (float(v) for v in compare.compare_gradients(
                compare.system_gradient(opt_state, b1), ref_grads))
            out.update(grad_norm_rel_diff=abs(ns - nr) / nr, grad_cosine=cos)
            if "gradient" in limits["compare"]:
                ok = (ok and out["grad_norm_rel_diff"]
                      <= limits["grad_norm_rel_tol"]
                      and cos >= limits["grad_cosine_min"])
        out["within_tolerance"] = bool(ok)
        return out

    def load_of(params, batch):
        load = parts_of(params, batch)[1]["tokens_per_expert"]
        return {"mean": round(float(load.mean()), 1), "max": int(load.max()),
                "min": int(load.min()),
                "rows_present_by_layer": load.sum(axis=1).tolist()}

    for seed in (int(s) for s in args.seeds.split(",")):
        key = jax.random.PRNGKey(seed % 2**32)
        tokens = traffic_mod.resident_tokens(seed, spec, model["vocab_size"])
        batch = {"input_ids": jax.device_put(tokens[:, :-1]),
                 "labels": jax.device_put(tokens[:, 1:])}
        params, opt_state = make_state(key)
        ref_loss, ref_grads = reference.over_microbatches(
            model, params, tokens, limits["microbatch"], bool(args.grad),
            jax.device_put)
        ref_loss = float(ref_loss)
        line = {"seed": seed, "device": jax.devices()[0].device_kind,
                "reference_loss": ref_loss,
                "expert_tokens": load_of(params, batch)}
        line.update(step_against(params, opt_state, batch, ref_loss,
                                 ref_grads))
        if args.control:
            params, opt_state = make_state(key)
            params = jax.tree.map(chop, params)   # the unrounded copy goes
            line["control"] = step_against(params, opt_state, batch, ref_loss,
                                           ref_grads)
        del ref_grads, params, opt_state
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
        if args.out:
            os.makedirs(os.path.join(ROOT, args.out), exist_ok=True)
            with open(os.path.join(ROOT, args.out, "chip_compare.jsonl"),
                      "a") as f:
                f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
