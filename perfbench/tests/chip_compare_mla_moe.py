"""The comparison that decides ``correct`` in a cell of the latent-attention,
routed-expert family, made over many seeds in one process, with its control:
what a builder runs on the chip to set the limits in the configuration file.

    python3 perfbench/tests/chip_compare_mla_moe.py \
        --config perfbench/configs/joyai-llm-flash.json \
        --traffic perfbench/traffic/step-8k.json --seeds 1,2,3 [--grad 1] \
        [--layers 2] [--control 1]

For each seed: the state from the seed as the worker makes it, the float32
reference over the seeded batch, one real step, and the differences the
worker would compute (loss; with ``--grad 1`` the gradient's norm and cosine,
from Adam's first moment). With ``--control 1`` the step is made again from
weights kept to 3 bits of mantissa (what fp8 e4m3 holds: the nearest
precision below bfloat16), against the reference on the unrounded weights:
that has to come out past a limit. ``--layers`` overrides
``num_hidden_layers`` (the gradient of the full depth does not fit beside
the state). Also printed: the share of token-expert pairs that change when
the first expert layer's router reads a hidden state rounded to bfloat16
instead of float32 (the state is the normed embedding of the batch, which
stands in for a layer's input), and the builder's reading of the experts'
load: the two loss terms and the tokens each held expert received on the
cell's batch (the auxiliary output of the program's loss, which the
benchmark's worker does not read back). With ``--steps n`` the losses of n
steps are printed beside those of training driven by the reference's
gradient through the same optimizer (at a depth whose float32 gradient
fits). With ``--load-steps n`` the experts' load is read before each of n
steps on the one batch (the forward pass with its parts, then the step): the
mean, most and least tokens a held expert receives, over the expert layers:
what the cell's timed steps see as the routing drifts onto the held experts.
One JSON line a seed.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--config", required=True)
    parser.add_argument("--traffic", required=True)
    parser.add_argument("--seeds", default="0")
    parser.add_argument("--grad", type=int, default=0)
    parser.add_argument("--control", type=int, default=0)
    parser.add_argument("--layers", type=int, default=None)
    parser.add_argument("--steps", type=int, default=0)
    parser.add_argument("--load-steps", type=int, default=0)
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp

    from perfbench import compare, traffic as traffic_mod, worker
    from ray_tpu.ops import moe

    with open(os.path.join(ROOT, args.config)) as f:
        model = json.load(f)
    spec = traffic_mod.load(os.path.join(ROOT, args.traffic))
    if args.layers is not None:
        model["num_hidden_layers"] = args.layers
    family = worker.load_family(ROOT, model)
    reference = worker.load_reference(ROOT, model)
    built = family.build(model, spec, None)
    make_state = jax.jit(built.make_state)
    parts_of = jax.jit(built.loss_with_parts)
    b1 = model["train"]["adam_b1"]

    def chop(x):
        if x.ndim < 2:
            return x
        mantissa, exponent = jnp.frexp(x)
        return jnp.ldexp(jnp.round(mantissa * 16) / 16, exponent)

    def step_against(params, opt_state, batch, ref_loss, ref_grads):
        _, opt_state, loss = built.step(params, opt_state, batch)
        out = {"loss": float(loss),
               "loss_rel_diff": abs(float(loss) - ref_loss) / abs(ref_loss)}
        limits = model["reference"]
        ok = out["loss_rel_diff"] <= limits["loss_rel_tol"]
        if ref_grads is not None:
            ns, nr, cos = (float(v) for v in compare.compare_gradients(
                compare.system_gradient(opt_state, b1), ref_grads))
            out.update(grad_norm_rel_diff=abs(ns - nr) / nr, grad_cosine=cos)
            if "gradient" in limits["compare"]:
                ok = (ok and out["grad_norm_rel_diff"]
                      <= limits["grad_norm_rel_tol"]
                      and cos >= limits["grad_cosine_min"])
        # the worker's verdict under the configuration's limits: true for
        # the step, false for the control
        out["within_tolerance"] = bool(ok)
        return out

    def pairs_moved(params, ids):
        layer = next(v["moe"] for k, v in sorted(params.items())
                     if k.startswith("layers_") and "moe" in v)
        x = jnp.take(params["embed"]["embedding"], ids.reshape(-1), axis=0)
        x = x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + 1e-6)
        k = model["num_experts_per_tok"]
        route = lambda h: moe.topk_routing(
            h, layer["router"], layer["router_bias"], k)[0]
        exact, rounded = route(x), route(x.astype(jnp.bfloat16))
        same = (exact[:, :, None] == rounded[:, None, :]).any(-1)
        return float(1.0 - same.mean())

    def trajectories(key, tokens, batch, steps):
        """The losses of ``steps`` steps on the one batch: the system's, and
        those of training driven by the float32 reference's gradient
        through the same optimizer. The first agree to rounding; the later
        ones show whether the step's update is the reference's."""
        import optax

        from ray_tpu.models import gpt2

        tx = gpt2.make_optimizer()
        params, opt_state = make_state(key)
        system = []
        for _ in range(steps):
            params, opt_state, loss = built.step(params, opt_state, batch)
            system.append(float(loss))
        params, _ = make_state(key)
        opt_state = tx.init(params)
        plain = []
        for _ in range(steps):
            loss, grads = reference.over_microbatches(
                model, params, tokens, model["reference"]["microbatch"],
                True, jax.device_put)
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            plain.append(float(loss))
        return {"system": system, "reference": plain}

    def load_over_steps(key, batch, steps):
        """Before each step: the loss and the tokens a held expert receives
        (mean, most, least over the expert layers and their experts)."""
        params, opt_state = make_state(key)
        out = {"loss": [], "mean": [], "max": [], "min": []}
        for _ in range(steps):
            loss, parts = parts_of(params, batch)
            load = parts["tokens_per_expert"]
            out["loss"].append(round(float(loss), 4))
            out["mean"].append(round(float(load.mean()), 1))
            out["max"].append(int(load.max()))
            out["min"].append(int(load.min()))
            params, opt_state, _ = built.step(params, opt_state, batch)
        return out

    for seed in (int(s) for s in args.seeds.split(",")):
        key = jax.random.PRNGKey(seed % 2**32)
        tokens = traffic_mod.resident_tokens(seed, spec, model["vocab_size"])
        batch = {"input_ids": jax.device_put(tokens[:, :-1]),
                 "labels": jax.device_put(tokens[:, 1:])}
        params, opt_state = make_state(key)
        ref_loss, ref_grads = reference.over_microbatches(
            model, params, tokens, model["reference"]["microbatch"],
            bool(args.grad), jax.device_put)
        ref_loss = float(ref_loss)
        line = {"seed": seed, "layers": model["num_hidden_layers"],
                "device": jax.devices()[0].device_kind,
                "reference_loss": ref_loss,
                "pairs_moved_by_bfloat16": pairs_moved(params,
                                                       batch["input_ids"])}
        _, parts = parts_of(params, batch)
        load = parts["tokens_per_expert"]
        line.update(loss_main=float(parts["main"]),
                    loss_mtp=float(parts["mtp"]),
                    expert_tokens={"max": int(load.max()),
                                   "min": int(load.min()),
                                   "mean": float(load.mean()),
                                   "by_layer_max": load.max(axis=1).tolist()})
        line.update(step_against(params, opt_state, batch, ref_loss,
                                 ref_grads))
        if args.control:
            params, opt_state = make_state(key)
            params = jax.tree.map(chop, params)
            line["control"] = step_against(params, opt_state, batch,
                                           ref_loss, ref_grads)
        del ref_grads
        if args.steps:
            line["losses"] = trajectories(key, tokens, batch, args.steps)
        if args.load_steps:
            del params, opt_state
            line["load_over_steps"] = load_over_steps(key, batch,
                                                      args.load_steps)
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
