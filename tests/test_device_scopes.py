"""Every family's train step carries the classes of the device's work
(``steptrace.device_scope``): the jaxpr of the step over the family's
``loss_fn`` at its tiny test size has an ``rt.<kind>`` segment in the name
stack of every equation that does the step's work (a matrix product, a
kernel, a loop), walked through sub-jaxprs. It guards, without a chip, what
``perfbench/opscopes.py`` reads from a trace on one: a family whose step is
unnamed has no by-class account."""

import importlib
import re

import jax
import jax.numpy as jnp
import optax
import pytest
from jax._src import core

from ray_tpu._private import steptrace

FAMILIES = {
    "gpt2": "GPT2Config", "llama": "LlamaConfig", "mla_moe": "MLAMoEConfig",
    "afmoe": "AfmoeConfig", "phi4flash": "Phi4FlashConfig",
    "lfm2": "Lfm2Config", "qwen3_next": "Qwen3NextConfig",
    "nemotron_h": "NemotronHConfig", "mellum": "MellumConfig",
    "sdar": "SdarConfig", "keye": "KeyeConfig",
}
# the equations that are the step's work: matrix products, kernels, loops
WORK = {"dot_general", "ragged_dot", "ragged_dot_general",
        "conv_general_dilated", "pallas_call", "custom_call", "while", "scan",
        "sort", "gather", "scatter-add", "cumsum"}
CLASS = re.compile(r"(?:^|[/(])rt\.(\w+)")


def _walk(jaxpr, above=""):
    """(primitive, whole name stack) of every equation: an equation inside
    a sub-jaxpr (a jit, a loop's body, a recomputed block) stands under its
    enclosing equation's stack, as the lowering joins them."""
    for eqn in jaxpr.eqns:
        stack = "/".join(s for s in (above, str(eqn.source_info.name_stack))
                         if s)
        yield eqn.primitive.name, stack
        if eqn.primitive.name != "pallas_call":   # a kernel's body is its own
            for sub in core.jaxprs_in_params(eqn.params):
                yield from _walk(sub, stack)


def _step_equations(family: str):
    module = importlib.import_module("ray_tpu.models." + family)
    config = getattr(module, FAMILIES[family]).small_test(remat=True)
    made = {}

    def abstract(key):   # shapes alone: nothing is initialised or compiled
        made["model"], params = module.init_params(config, key)
        return params

    params = jax.eval_shape(abstract, jax.random.PRNGKey(0))
    tx = optax.adamw(1e-3)
    step = module.build_train_step(made["model"], tx)
    ids = jnp.zeros((2, 32), jnp.int32)
    batch = {"input_ids": ids, "labels": ids}
    if family == "keye":
        batch["position_ids"] = jnp.zeros((3, 2, 32), jnp.int32)
    traced = step.trace(params, jax.eval_shape(tx.init, params), batch)
    return list(_walk(traced.jaxpr.jaxpr))


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_every_working_equation_of_the_step_carries_a_class(family):
    equations = _step_equations(family)
    work = [(p, s) for p, s in equations if p in WORK]
    assert len(work) > 20, len(work)
    bare = [(p, s) for p, s in work if not CLASS.search(s)]
    assert not bare, bare[:5]
    seen = {CLASS.findall(s)[-1] for _, s in equations if CLASS.search(s)}
    assert seen <= set(steptrace.DEVICE_SCOPES), seen
    # the step's own parts and every block's are there
    assert {"mixer", "norm", "vocab", "optimizer"} <= seen, seen
    assert seen & {"experts", "mlp"}, seen
    # the optimizer's update is the optimizer's alone
    assert all(CLASS.findall(s)[-1] == "optimizer" for _, s in equations
               if "rt.optimizer" in s)


def test_a_word_outside_the_vocabulary_is_refused():
    assert steptrace.DEVICE_SCOPES == (
        "mixer", "experts", "mlp", "norm", "vocab", "optimizer")
    with pytest.raises(ValueError):
        steptrace.device_scope("bogus")
    with pytest.raises(ValueError):
        steptrace.device_scope("rt.mixer")


def test_a_scope_is_metadata_and_nothing_else():
    """The jaxpr under a scope is the jaxpr without it but for name
    stacks, and a scope writes no record."""
    def inside(x):
        with steptrace.device_scope("mixer"):
            return jnp.tanh(x) @ x

    def outside(x):
        return jnp.tanh(x) @ x

    x = jnp.ones((4, 4))
    scoped, bare = jax.make_jaxpr(inside)(x), jax.make_jaxpr(outside)(x)
    assert str(scoped) == str(bare)
    # (a traced function may leave ``compile`` records where an earlier test
    # installed the listener: the scope alone is what must write nothing)
    before = steptrace.record_calls()
    with steptrace.device_scope("norm"):
        pass
    assert steptrace.record_calls() == before
    assert [s for _, s in _walk(scoped.jaxpr)] == ["rt.mixer", "rt.mixer"]
    assert [s for _, s in _walk(bare.jaxpr)] == ["", ""]
