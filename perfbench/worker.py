"""The benchmark's train loop: what ``JaxTrainer.fit()`` runs in the worker,
the only process that touches jax and the chip.

One loop serves every cell. What a cell is comes as data: the model's
sizes (a file under ``configs/``), the traffic's parameters (a file under
``traffic/``) and the names of its per-layer metrics (readers under
``metrics/``). The loop makes the train state on the device(s) from the
seed, compiles the one step shape ahead of time, holds the step against
the plain float32 reference, warms up, then either measures whole periods
for ``--seconds`` (``--trace 0``) or traces a fixed number of steps
(``--trace 1``). Everything it learns goes back through ``train.report``.

Nothing here is imported by the driver except the function itself, and
jax is imported only inside it.
"""

from __future__ import annotations

import importlib.util
import math
import os
import sys
import time

SPAN_DATA, SPAN_STEP, SPAN_CKPT = "bench/data", "bench/step", "bench/ckpt"


class _Phases:
    """Set-up phases on the host's clock (epoch seconds, shared with the
    driver, which is on the same machine)."""

    def __init__(self):
        self.marks = [("loop_entered", time.time())]

    def mark(self, name: str) -> None:
        self.marks.append((name, time.time()))


class _Reading:
    """What a per-layer metric's reader is handed."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def _load_reader(root: str, directory: str, name: str):
    """The reader of per-layer metric ``name``: ``<name>.py``, or for a
    quantity split by cell (``host_gap_ms.job``) the quantity's own
    ``host_gap_ms.py``."""
    path = os.path.join(root, directory, name + ".py")
    if not os.path.exists(path):
        path = os.path.join(root, directory, name.split(".")[0] + ".py")
    spec = importlib.util.spec_from_file_location(
        "perfbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _device_report(jax) -> dict:
    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def _planned_bytes(compiled) -> int:
    plan = compiled.memory_analysis()
    return int(plan.temp_size_in_bytes + plan.argument_size_in_bytes
               + plan.output_size_in_bytes - plan.alias_size_in_bytes)


def _allocator_peak(jax) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") or 0
             for d in jax.local_devices()]
    return int(max(peaks))


def _cache_entries(path) -> int:
    try:
        return sum(1 for name in os.listdir(path) if name.endswith("-cache"))
    except (FileNotFoundError, TypeError):
        return 0


def _state_shardings(jax, shapes, mesh, fsdp: bool):
    """Shardings for (params, opt_state) shapes by the rule of
    ``gpt2.shard_train_state``: parameters by ``shard_params_fsdp`` (or
    replicated), every parameter-shaped subtree of the optimizer state
    like the parameters, the rest replicated."""
    from ray_tpu.parallel import mesh_utils

    p_shapes, o_shapes = shapes
    rep = mesh_utils.replicated(mesh)
    p_sh = (mesh_utils.shard_params_fsdp(p_shapes, mesh) if fsdp
            else jax.tree.map(lambda _: rep, p_shapes))
    p_def = jax.tree_util.tree_structure(p_shapes)

    def params_like(node):
        return jax.tree_util.tree_structure(node) == p_def

    o_sh = jax.tree.map(
        lambda node: p_sh if params_like(node)
        else jax.tree.map(lambda _: rep, node),
        o_shapes, is_leaf=params_like)
    return p_sh, o_sh


def _checksums(jax, jnp):
    """Jitted tree -> uint32 [leaves, 2]: per leaf the sum of its words and
    a position-weighted sum, both modulo 2**32 (perfbench/readback.py
    computes the same from the file)."""
    words = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}

    def one(x):
        w = jax.lax.bitcast_convert_type(
            x, words[x.dtype.itemsize]).astype(jnp.uint32).reshape(-1)
        k = jax.lax.iota(jnp.uint32, w.shape[0]) % jnp.uint32(65521) + 1
        return jnp.stack([w.sum(dtype=jnp.uint32),
                          (w * k).sum(dtype=jnp.uint32)])

    return jax.jit(lambda tree: jnp.stack(
        [one(x) for x in jax.tree.leaves(tree)]))


def train_loop(config):
    phases = _Phases()
    root = config["root"]
    if root not in sys.path:
        sys.path.insert(0, root)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from perfbench import flops, readback, reference, xplane
    from ray_tpu import train
    from ray_tpu.air.checkpoint import Checkpoint, save_pytree
    from ray_tpu.models import gpt2

    model_cfg, traffic = config["model"], config["traffic"]
    layout, recipe = model_cfg["layout"], model_cfg["train"]
    chips, seed, rehearsal = config["chips"], config["seed"], config["rehearsal"]

    device = _device_report(jax)
    if not rehearsal and (device["platform"] != "tpu"
                          or device["count"] < chips):
        # no chip, or fewer than the cell names: say so and run nothing
        train.report({"summary": {"device": device, "refused": True}})
        return
    phases.mark("device_open")
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    cache_at_start = _cache_entries(cache_dir)

    compile_events = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compile_events.append((time.time(), name))
        if "/jax/core/compile" in name else None)

    mesh = None
    if layout.get("mesh"):
        from ray_tpu import parallel
        from ray_tpu.parallel import mesh_utils

        mesh = parallel.create_mesh(dict(layout["mesh"]))
        batch_sharding = mesh_utils.data_sharding(mesh)
        place = lambda a: jax.device_put(a, batch_sharding)
    else:
        place = jax.device_put

    # ------------------------------------------------------------------
    # traffic: where a step's batch comes from
    # ------------------------------------------------------------------
    batch_size, seq = traffic["batch"], traffic["seq"]
    tokens_per_step = batch_size * seq

    def to_batch(tokens):
        return {"input_ids": place(np.ascontiguousarray(tokens[:, :-1])),
                "labels": place(np.ascontiguousarray(tokens[:, 1:]))}

    if traffic["feed"] == "resident":
        first_tokens = np.asarray(config["resident_tokens"], dtype=np.int32)
        resident = to_batch(first_tokens)
        jax.block_until_ready(resident)

        def next_batch():
            return resident
    elif traffic["feed"] == "dataset":
        shard = train.get_dataset_shard("train")

        def epochs():
            while True:  # a drained dataset starts its next epoch
                yield from shard.iter_batches(batch_size=batch_size,
                                              drop_last=True)

        rows = epochs()
        first_tokens = np.asarray(next(rows)["tokens"])
        pending = [to_batch(first_tokens)]

        def next_batch():
            if pending:
                return pending.pop()
            return to_batch(np.asarray(next(rows)["tokens"]))
    else:
        raise ValueError(f"unknown feed {traffic['feed']!r}")
    phases.mark("traffic")

    # ------------------------------------------------------------------
    # state on the device(s), from the seed, in one jitted call
    # ------------------------------------------------------------------
    cfg = gpt2.GPT2Config(
        vocab_size=model_cfg["vocab_size"],
        n_positions=model_cfg["n_positions"], n_embd=model_cfg["n_embd"],
        n_layer=model_cfg["n_layer"], n_head=model_cfg["n_head"],
        dtype=jnp.dtype(recipe["compute_dtype"]),
        remat=bool(traffic.get("remat")), attention=recipe["attention"],
        loss_chunks=recipe["loss_chunks"])
    model = gpt2.GPT2(cfg)
    tx = gpt2.make_optimizer()

    def make_state(key):
        params = gpt2.init_params(cfg, key)[1]
        return params, tx.init(params)

    key = jax.random.PRNGKey(seed % 2**32)
    if mesh is None:
        params, opt_state = jax.jit(make_state)(key)
    else:
        shardings = _state_shardings(jax, jax.eval_shape(make_state, key),
                                     mesh, layout["fsdp"])
        params, opt_state = jax.jit(make_state, out_shardings=shardings)(key)
        # the program's own placement decides; where it agrees with the
        # shardings above this moves nothing
        params, opt_state = gpt2.shard_train_state(
            params, opt_state, mesh, fsdp=layout["fsdp"])
    jax.block_until_ready((params, opt_state))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    if n_params != flops.num_params(model_cfg):
        raise RuntimeError(
            f"the program made {n_params} parameters, the configuration "
            f"file's sizes give {flops.num_params(model_cfg)}")
    phases.mark("state")

    # ------------------------------------------------------------------
    # the one step shape, compiled ahead of time: the plan that is read is
    # the plan of the program that runs
    # ------------------------------------------------------------------
    step = gpt2.build_train_step(model, tx, donate=True)
    program = {"compiled": step.lower(params, opt_state,
                                      to_batch(first_tokens)).compile(),
               "compiles": 1}
    phases.mark("compile_or_cache_load")

    # ------------------------------------------------------------------
    # the plain reference on the first step's batch, before the first step
    # donates the parameters it reads
    # ------------------------------------------------------------------
    ref_cfg = model_cfg["reference"]
    want_grad = "gradient" in ref_cfg["compare"]
    ref_loss, ref_grads = reference.over_microbatches(
        model_cfg, params, first_tokens, ref_cfg["microbatch"], want_grad,
        place)
    ref_loss = float(ref_loss)
    phases.mark("reference")

    # ------------------------------------------------------------------
    # the loop a user writes
    # ------------------------------------------------------------------
    losses, saves = [], []
    save_every = traffic.get("save_every_steps", 0)
    save_dir = os.path.join(config["storage"], "worker_saves")
    checksum = _checksums(jax, jnp) if save_every else None
    state = {"params": params, "opt_state": opt_state}
    del params, opt_state

    def one_step():
        with jax.profiler.TraceAnnotation(SPAN_DATA):
            b = next_batch()
        with jax.profiler.TraceAnnotation(SPAN_STEP):
            state["params"], state["opt_state"], loss = program["compiled"](
                state["params"], state["opt_state"], b)
            program["batch"] = b
            loss = float(loss)  # the fence: the step is done
            train.report({"step": len(losses), "loss": loss})
        losses.append(loss)

    def save():
        sums = np.asarray(checksum(state))
        start = time.perf_counter()
        with jax.profiler.TraceAnnotation(SPAN_CKPT):
            target = os.path.join(save_dir, f"save_{len(saves):04d}")
            save_pytree(state, target, name="state")
            train.report({"step": len(losses), "loss": losses[-1],
                          "saved": len(saves)},
                         checkpoint=Checkpoint.from_directory(target))
        saves.append({"index": len(saves), "after_step": len(losses),
                      "stall_s": time.perf_counter() - start,
                      "sums": sums.tolist()})

    def state_shardings():
        return jax.tree.map(lambda x: x.sharding, state)

    def warm_step():
        """A step, then what ``jax.jit`` does unseen when a step hands its
        state back in other shardings than it took: compile for those.
        ``build_train_step`` pins no output shardings, so under a mesh the
        compiler chooses them."""
        before = state_shardings()
        one_step()
        if state_shardings() != before:
            program["compiled"] = step.lower(
                state["params"], state["opt_state"], program["batch"]).compile()
            program["compiles"] += 1

    # warm-up: the first step also yields what is held against the reference
    warm_step()
    comparison = {"loss": losses[0], "reference_loss": ref_loss,
                  "loss_rel_diff": abs(losses[0] - ref_loss) / abs(ref_loss)}
    ok_reference = comparison["loss_rel_diff"] <= ref_cfg["loss_rel_tol"]
    if want_grad:
        # after the first AdamW step from zero moments, mu = (1 - b1) * g
        mu = next(s.mu for s in state["opt_state"] if hasattr(s, "mu"))
        b1 = recipe["adam_b1"]
        system_grads = jax.tree.map(lambda m: m / (1.0 - b1), mu)
        ns, nr, cos = (float(x) for x in reference.compare_gradients(
            system_grads, ref_grads))
        comparison.update(grad_norm=ns, reference_grad_norm=nr,
                          grad_norm_rel_diff=abs(ns - nr) / nr,
                          grad_cosine=cos)
        ok_reference = (ok_reference
                        and comparison["grad_norm_rel_diff"]
                        <= ref_cfg["grad_norm_rel_tol"]
                        and cos >= ref_cfg["grad_cosine_min"])
        del mu, system_grads, ref_grads
    comparison["within_tolerance"] = bool(ok_reference)
    phases.mark("first_step_and_comparison")
    for _ in range(traffic["warmup_steps"] - 1):
        warm_step()
    plan_bytes = _planned_bytes(program["compiled"])
    if save_every:
        phases.mark("warm_up_steps")
        save()  # so that the first measured save is not the first ever
        phases.mark("first_save")
        for _ in range(traffic.get("warmup_steps_after_save", 0)):
            one_step()
    warm_steps, warm_saves = len(losses), len(saves)
    phases.mark("warm_up")

    # ------------------------------------------------------------------
    # measure whole periods, or trace a fixed number of steps
    # ------------------------------------------------------------------
    period = save_every or 1

    def one_period():
        for _ in range(period):
            one_step()
        if save_every:
            save()

    trace_dir, traced = config.get("trace_dir"), None
    t0_epoch, t0 = time.time(), time.perf_counter()
    if config["trace"]:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        t0_epoch, t0 = time.time(), time.perf_counter()
        for _ in range(max(1, traffic["traced_steps"] // period)):
            one_period()
        elapsed = time.perf_counter() - t0
        jax.profiler.stop_trace()
        periods = (len(losses) - warm_steps) // period
    else:
        seconds, periods, elapsed = config["seconds"], 0, 0.0
        while True:
            one_period()
            now = time.perf_counter() - t0
            if now > seconds and periods:
                break  # this one ended outside the window: not counted
            periods, elapsed = periods + 1, now
            if now > 2 * seconds:
                raise RuntimeError(
                    f"one period took {now:.1f} s, more than twice "
                    f"--seconds {seconds}: a failed run")
            if now + now / periods > seconds:
                break  # another whole period would not fit
    t_end_epoch = t0_epoch + elapsed
    steps = periods * period
    interval_losses = losses[warm_steps:warm_steps + steps]
    interval_saves = saves[warm_saves:warm_saves + (periods if save_every
                                                    else 0)]
    compiles_inside = [n for t, n in compile_events
                       if t0_epoch <= t <= t_end_epoch]

    summary = {
        "device": device, "worker_pid": os.getpid(),
        "phases": phases.marks, "t0_epoch": t0_epoch,
        "interval_s": elapsed, "periods": periods, "steps": steps,
        "tokens": steps * tokens_per_step,
        "tokens_per_step": tokens_per_step,
        "first_losses": losses[:8],
        "nonfinite_steps": sum(not math.isfinite(x) for x in interval_losses),
        "saves": interval_saves, "saves_before_interval": warm_saves,
        "leaf_paths": list(readback.leaves_by_name(state)) if save_every
        else [],  # in the order of the checksums
        "comparison": comparison,
        "compiles_inside_interval": compiles_inside,
        "step_compiles": program["compiles"],
        "plan_bytes": plan_bytes, "allocator_peak_bytes": _allocator_peak(jax),
        "n_params": int(n_params),
        "cache_dir": cache_dir, "cache_entries": [cache_at_start,
                                                  _cache_entries(cache_dir)],
    }

    if config["trace"]:
        path = xplane.find_xplane(trace_dir)
        traced = xplane.load(path)
        peaks = (flops.peaks(device["kind"])
                 if device["platform"] == "tpu" else None)
        reading = _Reading(
            trace=traced, chips=chips, peaks=peaks, plan_bytes=plan_bytes,
            flops_per_token=flops.train_flops_per_token(model_cfg, seq),
            host={"gang_start_s": phases.marks[0][1] - config["fit_called"],
                  "traced_tokens": steps * tokens_per_step,
                  "traced_wall_s": elapsed},
            model=model_cfg, traffic=traffic)
        per_layer = {}
        for name in config["per_layer"]:
            value = _load_reader(root, config["metrics_dir"], name).read(
                reading)
            if value is not None:
                per_layer[name] = float(value)
        bw = xplane.busy_and_window_s(traced)
        summary.update(
            per_layer=per_layer,
            busy_s=bw[0] if bw else None, window_s=bw[1] if bw else None,
            breakdown=xplane.breakdown(traced),
            xplane={"path": path, "bytes": os.path.getsize(path),
                    "chips": sorted(traced.ops), "spans": len(traced.spans),
                    "ops_chip0": len(traced.ops.get(0, []))})
    train.report({"summary": summary})
