"""Pipeline parallelism: GPipe-style microbatched stages over a mesh axis.

The reference exercises pipeline parallelism only through external stacks
run on Ray (ray: release/alpa_tests/train_opt_2_7b_minimum.py; SURVEY
§2.9 marks PP "first-class to build" for the TPU framework). TPU-native
design: stages live on a ``pipeline`` mesh axis; every device holds ONE
stage's parameters (leading stage axis sharded over the mesh axis) and a
rotating activation buffer that ``lax.ppermute`` advances one hop per tick
— the classic collective-permute pipeline from the JAX/praxis playbook,
not a port of torch's send/recv stage graphs.

Schedule: with S stages and M microbatches, tick t ∈ [0, M+S-1):
  - stage 0 injects microbatch t (while t < M),
  - every device applies its stage to its current activation,
  - activations rotate to the next stage over ICI,
  - the last stage emits microbatch t-(S-1) starting at t = S-1.
Utilization is M/(M+S-1) (the pipeline bubble); reverse-mode AD flows
through ppermute (its transpose is the reverse permute), so one
``jax.grad`` of the pipelined loss trains all stages without any
hand-written backward schedule.

All functions here run INSIDE ``shard_map`` (they use ``lax.axis_index``/
``ppermute`` on ``axis_name``); ``build_pipeline_fn`` wraps the common
replicated-input case.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec


def pipeline_apply(stage_fn: Callable, stage_params, microbatches,
                   *, axis_name: str = "pipeline"):
    """Apply an S-stage pipeline to M microbatches. Call inside shard_map.

    stage_fn(params, x) -> y: one stage's computation; y must have x's
      shape (activations flow stage to stage unchanged in shape).
    stage_params: this device's stage parameters (stage axis already
      sharded away by the caller's in_specs).
    microbatches: (M, ...) array, replicated across the pipeline axis.

    Returns (M, ...) outputs, replicated across the pipeline axis.
    """
    S = lax.axis_size(axis_name)
    M = microbatches.shape[0]
    idx = lax.axis_index(axis_name)
    is_first = idx == 0
    is_last = idx == S - 1
    perm = [(i, (i + 1) % S) for i in range(S)]

    # mark the carries as device-varying over the pipeline axis up front:
    # the loop body makes them varying (axis_index/ppermute), and scan
    # requires carry types to be loop-invariant
    state = lax.pcast(jnp.zeros_like(microbatches[0]), (axis_name,),
                      to="varying")
    outputs = lax.pcast(jnp.zeros_like(microbatches), (axis_name,),
                        to="varying")

    def tick(t, carry):
        state, outputs = carry
        # stage 0 takes microbatch t from the feed (clamped once drained)
        feed = lax.dynamic_index_in_dim(
            microbatches, jnp.clip(t, 0, M - 1), axis=0, keepdims=False
        )
        x = jnp.where(is_first, feed, state)
        y = stage_fn(stage_params, x)
        # the last stage has finished microbatch t-(S-1) once t >= S-1;
        # other devices (and warm-up ticks) must leave the buffer unchanged
        out_idx = jnp.clip(t - (S - 1), 0, M - 1)
        current = lax.dynamic_index_in_dim(outputs, out_idx, 0, keepdims=False)
        emit = jnp.logical_and(is_last, t >= S - 1)
        outputs = lax.dynamic_update_index_in_dim(
            outputs, jnp.where(emit, y, current), out_idx, 0
        )
        state = lax.ppermute(y, axis_name, perm)
        return state, outputs

    _, outputs = lax.fori_loop(0, M + S - 1, tick, (state, outputs))
    # replicate the last stage's outputs to every pipeline rank (zeros
    # elsewhere, so a psum is a broadcast); grads flow back through it
    return lax.psum(jnp.where(is_last, outputs, 0.0), axis_name)


def stack_stage_params(params_per_stage):
    """Stack a list of per-stage pytrees into one pytree with a leading
    stage axis — shard that axis over the pipeline mesh axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *params_per_stage)


def build_pipeline_fn(stage_fn: Callable, mesh: Mesh, *,
                      axis_name: str = "pipeline",
                      donate: bool = False) -> Callable:
    """jit(shard_map(...)) wrapper: (stacked_params, microbatches) ->
    outputs, with the stage axis of ``stacked_params`` sharded over
    ``axis_name`` and microbatches replicated."""

    def local(stacked, mb):
        # local stacked shape is (1, ...): this device's stage
        own = jax.tree.map(lambda p: p[0], stacked)
        return pipeline_apply(stage_fn, own, mb, axis_name=axis_name)

    stage_spec = PartitionSpec(axis_name)  # leading stage axis per leaf
    fn = shard_map(
        local, mesh=mesh,
        in_specs=(stage_spec, PartitionSpec()),
        out_specs=PartitionSpec(),
    )
    return jax.jit(fn, donate_argnums=(0,) if donate else ())
