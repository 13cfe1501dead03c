"""The course of the expert layers' row buffers over the steps a cell times:
the pairs present a layer, the rung each layer's buffer took and the fill
(``mla_moe.step_metrics``' counters, as a user's loop reads them), beside
each step's loss and wall time. A side script: it builds the cell's step
from the benchmark's own files with the loss's parts as the step's
auxiliary output, which the benchmark's worker does not read. Run on the
chip; prints one JSON line a step.

    python benches/moe_fill_course.py --workload joyai-llm-flash.step-8k \
        --seed 2147486901 --steps 36

``--bench-file perfbench/tests/rehearsal_mla_moe.json --workload
tiny-mla-moe.step`` rehearses it on the CPU.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--bench-file", default="BENCHMARK.json")
    parser.add_argument("--workload", default="joyai-llm-flash.step-8k")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--steps", type=int, default=36)
    args = parser.parse_args()

    import jax
    import numpy as np

    from perfbench import run, traffic as traffic_mod, worker
    from ray_tpu import parallel
    from ray_tpu.models import mla_moe
    from ray_tpu.ops import moe

    with open(os.path.join(ROOT, args.bench_file)) as f:
        loaded = run.load_cell(json.load(f), args.workload)
    model, spec = loaded["model"], loaded["traffic"]
    built = worker.load_family(ROOT, model).build(model, spec, None)
    step = parallel.build_train_step(
        built.loss_with_parts, mla_moe.make_optimizer(), donate=True,
        has_aux=True)
    tokens = traffic_mod.resident_tokens(args.seed, spec, model["vocab_size"])
    batch = {"input_ids": jax.device_put(tokens[:, :-1]),
             "labels": jax.device_put(tokens[:, 1:])}
    pairs = batch["input_ids"].size * model["num_experts_per_tok"]
    rungs = np.asarray(moe.row_buffer_rungs(pairs))
    params, opt_state = jax.jit(built.make_state)(
        jax.random.PRNGKey(args.seed % 2**32))
    for n in range(args.steps):
        start = time.perf_counter()
        params, opt_state, *out = step(params, opt_state, batch)
        metrics = mla_moe.step_metrics(*out, pairs=pairs)
        wall_ms = (time.perf_counter() - start) * 1e3
        present = np.asarray(out[-1]).sum(axis=-1)
        print(json.dumps({
            "step": n, "device": jax.devices()[0].device_kind,
            "step_wall_ms": round(wall_ms, 1),
            "loss": round(metrics["loss"], 4),
            "rows_present_by_layer": present.tolist(),
            "rows_buffered_by_layer": rungs[
                moe.row_buffer_rung(present, pairs)].tolist(),
            **{key: metrics[key] for key in (
                "rows_present", "rows_buffered", "rows_fill",
                "expert_tokens_mean", "expert_tokens_max")}}), flush=True)


if __name__ == "__main__":
    main()
