"""MoE / expert parallelism (SURVEY §2.9 EP).

Checks routing invariants, dense-vs-EP equivalence on the virtual
8-device mesh, and gradient flow through the EP all_to_all path.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.ops import moe

from ray_tpu.parallel.collectives import shard_map_norep


def test_switch_gating_invariants():
    key = jax.random.PRNGKey(0)
    logits = jax.random.normal(key, (32, 4))
    dispatch, combine, aux = moe.switch_gating(logits, capacity=8)
    # each token goes to at most one (expert, slot)
    assert float(dispatch.sum(axis=(1, 2)).max()) <= 1.0
    # no expert holds more than capacity tokens
    assert float(dispatch.sum(axis=(0, 2)).max()) <= 8.0
    # each (expert, slot) pair is used at most once
    assert float(dispatch.sum(axis=0).max()) <= 1.0
    assert np.isfinite(float(aux))
    # balanced capacity: with C=T no token drops
    dispatch_full, _, _ = moe.switch_gating(logits, capacity=32)
    assert float(dispatch_full.sum()) == 32.0


def test_moe_dense_forward_and_dropping():
    key = jax.random.PRNGKey(1)
    params = moe.init_moe_params(key, d_model=16, d_hidden=32, num_experts=4)
    x = jax.random.normal(jax.random.PRNGKey(2), (64, 16))
    out, aux = moe.moe_ffn(params, x, capacity_factor=2.0)
    assert out.shape == x.shape
    assert np.isfinite(np.asarray(out)).all() and np.isfinite(float(aux))


def test_moe_ep_matches_dense():
    """Expert-parallel execution over the 8-device mesh computes the same
    function as the all-local dense path."""
    devices = jax.devices()
    assert len(devices) == 8
    mesh = Mesh(np.array(devices).reshape(2, 4), ("data", "ep"))
    E, d, h = 8, 16, 32
    params = moe.init_moe_params(jax.random.PRNGKey(3), d, h, E)
    x = jax.random.normal(jax.random.PRNGKey(4), (128, d))

    dense_out, dense_aux = moe.moe_ffn(params, x, capacity_factor=8.0)

    ep_specs = {"router": P(), "wi": P("ep"), "wo": P("ep")}

    def body(params, x):
        out, aux = moe.moe_ffn_ep(params, x, axis="ep", capacity_factor=8.0)
        return out, jax.lax.pmean(jax.lax.pmean(aux, "data"), "ep")

    fn = jax.jit(shard_map_norep(
        body, mesh=mesh,
        in_specs=({k: ep_specs[k] for k in params}, P("data")),
        out_specs=(P("data"), P()),
    ))
    params_sharded = {
        k: jax.device_put(v, NamedSharding(mesh, ep_specs[k]))
        for k, v in params.items()
    }
    x_sharded = jax.device_put(x, NamedSharding(mesh, P("data")))
    ep_out, ep_aux = fn(params_sharded, x_sharded)

    # Gating runs per data shard (capacity per shard), so with a capacity
    # factor large enough that nothing drops, outputs match exactly.
    np.testing.assert_allclose(
        np.asarray(ep_out), np.asarray(dense_out), rtol=2e-4, atol=2e-5
    )
    assert np.isfinite(float(ep_aux))


def test_moe_ep_gradients_flow():
    devices = jax.devices()
    mesh = Mesh(np.array(devices).reshape(2, 4), ("data", "ep"))
    E, d, h = 8, 8, 16
    params = moe.init_moe_params(jax.random.PRNGKey(5), d, h, E)
    x = jax.random.normal(jax.random.PRNGKey(6), (64, d))
    ep_specs = {"router": P(), "wi": P("ep"), "wo": P("ep")}

    def loss_body(params, x):
        def loss_fn(p):
            out, aux = moe.moe_ffn_ep(p, x, axis="ep", capacity_factor=4.0)
            return (out ** 2).mean() + 0.01 * aux  # aux exercises router grad

        return moe.ep_loss_and_grads(loss_fn, params, "data", "ep")

    fn = jax.jit(shard_map_norep(
        loss_body, mesh=mesh,
        in_specs=({k: ep_specs[k] for k in params}, P(("data", "ep"))),
        out_specs=(P(), {k: ep_specs[k] for k in params}),
    ))
    params_sharded = {
        k: jax.device_put(v, NamedSharding(mesh, ep_specs[k]))
        for k, v in params.items()
    }
    x_sharded = jax.device_put(x, NamedSharding(mesh, P(("data", "ep"))))
    loss, grads = fn(params_sharded, x_sharded)
    assert np.isfinite(float(loss))
    for k, g in grads.items():
        assert np.isfinite(np.asarray(g)).all(), k
    assert float(jnp.abs(grads["wi"]).sum()) > 0.0
    assert float(jnp.abs(grads["router"]).sum()) > 0.0


def test_moe_ep_gradients_match_dense():
    """The EP step's reduced gradients equal the dense single-device
    gradients of the same global-mean objective."""
    devices = jax.devices()
    mesh = Mesh(np.array(devices).reshape(2, 4), ("data", "ep"))
    E, d, h = 8, 8, 16
    params = moe.init_moe_params(jax.random.PRNGKey(7), d, h, E)
    x = jax.random.normal(jax.random.PRNGKey(8), (64, d))
    ep_specs = {"router": P(), "wi": P("ep"), "wo": P("ep")}

    # aux is intentionally shard-local (per-shard load stats), so exact
    # parity holds for the data term; aux grad flow is covered above.
    def dense_loss(p):
        out, _ = moe.moe_ffn(p, x, capacity_factor=8.0)
        return (out ** 2).mean()

    dense_grads = jax.grad(dense_loss)(params)

    def loss_body(p, xs):
        def local_loss(pp):
            out, _ = moe.moe_ffn_ep(pp, xs, axis="ep", capacity_factor=8.0)
            return (out ** 2).mean()

        _, grads = moe.ep_loss_and_grads(local_loss, p, "data", "ep")
        return grads

    fn = jax.jit(shard_map_norep(
        loss_body, mesh=mesh,
        in_specs=({k: ep_specs[k] for k in params}, P(("data", "ep"))),
        out_specs={k: ep_specs[k] for k in params},
    ))
    params_sharded = {
        k: jax.device_put(v, NamedSharding(mesh, ep_specs[k]))
        for k, v in params.items()
    }
    x_sharded = jax.device_put(x, NamedSharding(mesh, P(("data", "ep"))))
    ep_grads = fn(params_sharded, x_sharded)
    for k in params:
        np.testing.assert_allclose(
            np.asarray(ep_grads[k]), np.asarray(dense_grads[k]),
            rtol=5e-4, atol=1e-6,
        )


# ----------------------------------------------------------------------
# the kernel ``to_tokens`` (interpreted here) against ``_sum_of_pairs``
# ----------------------------------------------------------------------

_T, _K, _HELD, _D = 64, 4, 4, 128


def _routing(case):
    """(T, k) experts of a layer that holds experts 0 .. 3, each token's in
    ascending order (so that the kernel, which sums a token's pairs by
    expert, and ``_sum_of_pairs``, which sums them by choice, add alike)."""
    rng = np.random.default_rng(5)
    absent = np.stack([_HELD + np.sort(rng.permutation(12)[:_K])
                       for _ in range(_T)])
    if case == "none_is_mine":
        experts = absent
    elif case == "every_pair_is_mine":
        experts = np.tile(np.arange(_HELD), (_T, 1))
    elif case == "one_expert_takes_every_token":
        experts = np.concatenate([np.full((_T, 1), 2), absent[:, 1:]], axis=1)
    elif case == "tokens_with_all_k_held":
        experts = np.where(rng.random((_T, 1)) < 0.3,
                           np.arange(_HELD), absent)
    else:
        assert case == "ranges_start_off_a_tile"
        experts = np.stack([np.sort(rng.permutation(16)[:_K])
                            for _ in range(_T)])
    return jnp.asarray(experts.astype(np.int32))


@pytest.mark.parametrize("weighted", [False, True],
                         ids=["ones", "weights"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", [
    "none_is_mine", "every_pair_is_mine", "one_expert_takes_every_token",
    "tokens_with_all_k_held", "ranges_start_off_a_tile"])
def test_the_kernel_back_to_the_tokens_is_the_sum_of_pairs(
        case, dtype, weighted):
    """``_placed`` in interpret mode equals ``_sum_of_pairs`` rounded once:
    to the bit (the weights are powers of two and a token's experts ascend,
    so both add the same float32 numbers in the same order), at blocks of
    tokens and chunks of rows that give a range one chunk, several, and a
    last chunk pulled back inside the buffer; with NaN in every row past the
    count, which is every row that no range covers, and which a chunk read
    for a range's end brings into VMEM."""
    experts = _routing(case)
    plan = moe._plan(experts, _HELD, 0)
    present = int(plan["present"])
    assert present == {"none_is_mine": 0, "every_pair_is_mine": _T * _K,
                       "one_expert_takes_every_token": _T}.get(case, present)
    keys = jax.random.split(jax.random.PRNGKey(present), 2)
    rows = jax.random.normal(keys[0], (_T * _K, _D), dtype)
    rows = rows.at[present:].set(jnp.nan)
    weights = 2.0 ** jax.random.randint(
        keys[1], (_T, _K), -3, 2).astype(jnp.float32) if weighted else None
    want = moe._sum_of_pairs(rows, plan, weights).astype(dtype)
    assert np.isfinite(np.asarray(want, np.float32)).all()
    if case == "tokens_with_all_k_held":
        assert (np.asarray(plan["mine"]).sum(axis=1) == _K).any()
    starts, counts = moe._block_ranges(plan, 16)
    assert int(counts.sum()) == present and int(counts.max()) <= 16
    if case == "ranges_start_off_a_tile":
        assert (np.asarray(starts)[np.asarray(counts) > 0] % 8 != 0).any()
    for block, chunk in ((16, 16), (32, 16), (64, 128)):
        got = moe._placed(rows, plan, weights, block, chunk, interpret=True)
        assert got.dtype == dtype and np.array_equal(got, want), (block, chunk)


def test_the_kernel_takes_whole_lane_tiles_and_whole_chunks_only():
    """``_token_blocks``: blocks of at most 512 tokens whose sum, result
    and chunks take at most 10 MiB of VMEM, chunks of 128 rows; no kernel for
    rows that are no whole lane tiles, a buffer that is no whole chunks, or
    tokens no block divides (the tests' toy layers, which keep the
    gathers)."""
    def blocks(tokens, k, d, dtype=jnp.bfloat16, length=None):
        return moe._token_blocks(
            jax.ShapeDtypeStruct((length or tokens * k, d), dtype),
            {"mine": jax.ShapeDtypeStruct((tokens, k), jnp.bool_)})
    assert blocks(32768, 4, 2048) == (512, 128)       # the cells' sizes
    assert blocks(16384, 8, 1024) == (512, 128)
    assert blocks(16384, 8, 2048, jnp.float32) == (256, 128)
    assert blocks(16384, 8, 4096) == (256, 128)
    assert blocks(16384, 8, 4096, jnp.float32) == (128, 128)
    assert blocks(16384, 8, 8192) is None
    assert blocks(384, 4, 128) == (128, 128)
    assert blocks(96, 3, 32) is None and blocks(16384, 8, 16) is None
    assert blocks(128, 4, 128, length=520) is None
    assert blocks(192, 4, 128) is None
    assert blocks(512, 4, 128, jnp.float16) is None


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_layer_under_the_kernel_is_the_layer_under_the_gathers(
        dtype, monkeypatch):
    """``held_expert_ffn`` where a TPU under no mesh would take the kernel
    (the backend's name and the fresh buffers stood in for, the kernel
    interpreted): result, counts and four gradients are the gathers', and
    each traced pass writes one ``moe/to_tokens`` record that says which."""
    from ray_tpu._private import steptrace
    T, k, d, width, held, of = 256, 4, 128, 32, 4, 4
    rng = np.random.default_rng(11)
    experts = jnp.asarray(np.stack(
        [rng.permutation(held * of)[:k] for _ in range(T)]).astype(np.int32))
    keys = jax.random.split(jax.random.PRNGKey(3), 5)
    x, target = (jax.random.normal(key, (T, d), dtype) for key in keys[:2])
    weights = jax.random.uniform(keys[2], (T, k), minval=0.1)
    wi = jax.random.normal(keys[3], (held, d, 2 * width)) * 0.2
    wo = jax.random.normal(keys[4], (held, width, d)) * 0.2

    def both_ways():
        def loss(x, weights, wi, wo):
            y, tokens = moe.held_expert_ffn(x, experts, weights, wi, wo,
                                            index=1, of=of)
            return (y * target).sum().astype(jnp.float32), (y, tokens)
        steptrace.set_enabled(True)
        steptrace.reset()
        try:
            jax.clear_caches()         # the layer's passes are jitted
            (_, aux), grads = jax.value_and_grad(
                loss, argnums=(0, 1, 2, 3), has_aux=True)(x, weights, wi, wo)
            drawn = [e["args"] for e in steptrace.chrome_trace(
                steptrace.merge_records(steptrace.snapshot()))
                if e["ph"] == "C" and e["name"] == "moe/to_tokens"]
        finally:
            steptrace.set_enabled(False)
        return aux + grads, drawn

    want, drawn = both_ways()
    record = {"slots": T * k, "tokens": T, "held": held}
    assert drawn == [
        dict(record, kernel=0, block=0, chunk=0, backward=backward)
        for backward in (0, 1)]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(moe, "_unwritten",
                        lambda shape, dtype: jnp.zeros(shape, dtype))
    monkeypatch.setattr(moe, "_placed", functools.partial(
        moe._placed, interpret=True))
    got, drawn = both_ways()
    monkeypatch.undo()
    jax.clear_caches()
    assert drawn == [
        dict(record, kernel=1, block=256, chunk=128, backward=backward)
        for backward in (0, 1)]
    assert int(got[1].sum()) == int(want[1].sum()) > 0
    # a token's pairs are summed by expert and not by choice: one rounding
    loose = dict(rtol=1e-5, atol=1e-5) if dtype == jnp.float32 else dict(
        rtol=2.0**-7, atol=1e-6)
    for a, b in zip(got, want):
        assert np.isfinite(np.asarray(a, np.float32)).all()
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), **loose)
