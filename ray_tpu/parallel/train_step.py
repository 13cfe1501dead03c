"""The train step and where its state lives: one builder, one placement rule.

A model brings its loss ``loss_fn(params, batch)`` and a rule for its
parameters' shardings (``mesh_utils.shard_params_fsdp``, a tensor-parallel
table, a pipeline's stage axis, experts over ``ep``). The step built here
takes its layout from the state it is called with, and the optimizer state
is laid out from the parameters' shardings by one rule: a moment lies as
its parameter does, everything else is replicated.
"""

from __future__ import annotations

import jax
import optax

from ray_tpu._private import steptrace
from ray_tpu.parallel.mesh_utils import replicated


class _StepByLayout:
    """A train step ``(params, opt_state, batch) -> (params, opt_state,
    loss)`` that takes its layout from its arguments. Called, traced or
    lowered with a state that lies on one device (or abstract and unplaced)
    it is ``jitted()``, the plain jit. With a state placed over several devices
    (``place_train_state``) it is ``jitted((param shardings, optimizer
    state shardings))``: the state comes back in the shardings it went in,
    so the second step finds the program of the first, and the gradients
    take the parameters' shardings. One jit a layout, kept; a loop that
    hands back what it was given pays a walk over the leaves a call."""

    def __init__(self, jitted):
        self._jitted = jitted
        self._by_layout = []  # [(leaf shardings, jit)]: one entry as a rule

    def _for(self, params, opt_state):
        state = (params, opt_state)
        layout = tuple(getattr(x, "sharding", None)
                       for x in jax.tree.leaves(state))
        for known, fn in self._by_layout:
            if known == layout:
                return fn
        spread = any(s is not None and len(s.device_set) > 1 for s in layout)
        fn = self._jitted(jax.tree.unflatten(
            jax.tree.structure(state), layout) if spread else None)
        self._by_layout.append((layout, fn))
        return fn

    def __call__(self, params, opt_state, batch):
        params, opt_state, out = self._for(params, opt_state)(
            params, opt_state, batch)
        # the loss, then the leaves of a loss function's auxiliary output
        return (params, opt_state, *jax.tree.leaves(out))

    def trace(self, params, opt_state, batch):
        return self._for(params, opt_state).trace(params, opt_state, batch)

    def lower(self, params, opt_state, batch):
        return self.trace(params, opt_state, batch).lower()


# The name ``_StepByLayout``'s step is jitted under, for whoever looks for its
# compilations in the step-telemetry ring (``steptrace`` records of kind
# ``compile``).
STEP_NAME = "step"


def build_train_step(loss_fn, tx, donate: bool = True, has_aux: bool = False,
                     with_count: bool = False):
    """Jitted ``(params, opt_state, batch) -> (params, opt_state, loss)``
    over ``loss_fn(params, batch)``; with ``has_aux`` the loss function
    returns ``(loss, aux)`` and the leaves of ``aux`` follow the loss. With
    ``with_count`` it is ``loss_fn(params, batch, count)``, ``count`` the
    optimizer's own count of the steps taken (``step_count``): the one
    clock a step has, for a loss that draws noise anew every step.

    Sharding is inferred from the placed arguments (``place_train_state``
    and a batch on ``mesh_utils.data_sharding`` first): with the batch
    split over data axes and params replicated (DP) or fsdp-sharded
    (ZeRO-3), the XLA partitioner inserts the gradient psum /
    reduce-scatter on ICI, the TPU-native replacement for the reference's
    NCCL-DDP allreduce. The state is returned in the shardings it came in
    (``_StepByLayout``); on one device that adds nothing to the program.
    """

    def jitted(shardings=None):
        def step(params, opt_state, batch):
            seen = (batch, step_count(opt_state)) if with_count else (batch,)
            out, grads = jax.value_and_grad(loss_fn, has_aux=has_aux)(
                params, *seen)
            with steptrace.device_scope("optimizer"):
                if shardings:
                    # a gradient leaves the backward pass laid out as its
                    # parameter is at rest: the sum over the split batch
                    # becomes a reduce-scatter, not an all-reduce
                    grads = jax.lax.with_sharding_constraint(grads,
                                                             shardings[0])
                updates, opt_state = tx.update(grads, opt_state, params)
                params = optax.apply_updates(params, updates)
            return params, opt_state, out

        step.__name__ = STEP_NAME
        return jax.jit(
            step, donate_argnums=(0, 1) if donate else (),
            out_shardings=(*shardings, None) if shardings else None)

    return _StepByLayout(jitted)


def step_count(opt_state):
    """The steps an optax state has taken: its first ``count`` leaf (Adam's;
    a schedule's runs beside it), int32."""
    return optax.tree_utils.tree_get_all_with_path(opt_state, "count")[0][1]


def state_shardings(params, opt_state, param_shardings):
    """``(param_shardings, optimizer state shardings)``: every subtree of
    ``opt_state`` shaped like ``params`` (a moment) takes the parameters'
    shardings, every other leaf (a step count) is replicated on the
    parameters' mesh. Reads tree structure alone, so abstract values
    (``jax.eval_shape``) do as well as arrays."""
    treedef = jax.tree.structure(params)
    like_params = lambda node: jax.tree.structure(node) == treedef
    rep = replicated(jax.tree.leaves(param_shardings)[0].mesh)
    return param_shardings, jax.tree.map(
        lambda node: param_shardings if like_params(node)
        else jax.tree.map(lambda _: rep, node),
        opt_state, is_leaf=like_params)


def place_train_state(params, opt_state, param_shardings):
    """Put params and optimizer state where ``state_shardings`` says. Step
    observatory: one span ``train/shard_state`` with the bytes of both
    trees as its count (GPT-2 XL: 18.7 GB), beside ``ckpt/persist`` in
    ``train_timeline``."""
    state = (params, opt_state)
    shardings = state_shardings(params, opt_state, param_shardings)
    nbytes = sum(getattr(leaf, "nbytes", 0) for leaf in jax.tree.leaves(state))
    with steptrace.span("train/shard_state", nbytes):
        return jax.tree.map(jax.device_put, state, shardings)
