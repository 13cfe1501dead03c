"""Attention kernel numerics (vs naive reference) on the virtual CPU mesh."""

import dataclasses
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops import (
    attention_reference,
    flash_attention,
    ring_self_attention,
)
from tests.conftest import kernel_calls, kernel_whiles


def _qkv(b=2, h=2, s=64, d=16, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (b, h, s, d)
    return tuple(jax.random.normal(k, shape, dtype) for k in ks)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_scan_matches_reference(causal):
    q, k, v = _qkv()
    ref = attention_reference(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, impl="scan", block_k=16)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_flash_scan_uneven_blocks():
    q, k, v = _qkv(s=48)
    ref = attention_reference(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, impl="scan", block_k=32)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_flash_scan_grad_matches_reference():
    q, k, v = _qkv(s=32)

    def loss_ref(q, k, v):
        return attention_reference(q, k, v, causal=True).sum()

    def loss_flash(q, k, v):
        return flash_attention(q, k, v, causal=True, impl="scan",
                               block_k=8).sum()

    g_ref = jax.grad(loss_ref)(q, k, v)
    g_out = jax.grad(loss_flash)(q, k, v)
    np.testing.assert_allclose(g_out, g_ref, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_reference(causal):
    from ray_tpu import parallel

    n = min(8, len(jax.devices()))
    mesh = parallel.create_mesh({"sp": n})
    q, k, v = _qkv(b=1, h=2, s=8 * n, d=16)
    ref = attention_reference(q, k, v, causal=causal)
    out = ring_self_attention(q, k, v, mesh, seq_axis="sp", causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_ring_attention_differentiable():
    from ray_tpu import parallel

    n = min(4, len(jax.devices()))
    mesh = parallel.create_mesh({"sp": n})
    q, k, v = _qkv(b=1, h=1, s=4 * n, d=8)

    def f_ring(q, k, v):
        return ring_self_attention(q, k, v, mesh, causal=True).sum()

    def f_ref(q, k, v):
        return attention_reference(q, k, v, causal=True).sum()

    g_ring = jax.grad(f_ring)(q, k, v)
    g_ref = jax.grad(f_ref)(q, k, v)
    np.testing.assert_allclose(np.asarray(g_ring), np.asarray(g_ref),
                               atol=1e-4, rtol=1e-4)


def test_gpt2_sequence_parallel_step():
    """End-to-end: GPT-2 with ring attention trains under a data x sp mesh
    and matches the single-device step numerically."""
    import jax.numpy as jnp

    from ray_tpu import parallel
    from ray_tpu.models import gpt2

    n = min(8, len(jax.devices()))
    if n < 4:
        pytest.skip("needs 4+ devices")
    mesh = parallel.create_mesh({"data": 2, "sp": n // 2})

    cfg_sp = gpt2.GPT2Config.small_test(attention="ring", dtype=jnp.float32)
    cfg_1d = gpt2.GPT2Config.small_test(dtype=jnp.float32)
    model_sp, params, tx, opt_state = gpt2.make_train_state(
        cfg_sp, jax.random.PRNGKey(0)
    )
    model_1d = gpt2.GPT2(cfg_1d)

    batch = gpt2.synthetic_batch(jax.random.PRNGKey(1), 4, 32,
                                 cfg_sp.vocab_size)
    step_sp = gpt2.build_train_step_sp(model_sp, tx, mesh, donate=False)
    p2, o2, loss_sp = step_sp(params, opt_state, batch)

    loss_1d = gpt2.loss_fn(params, model_1d, batch)
    assert jnp.isfinite(loss_sp)
    np.testing.assert_allclose(
        float(loss_sp), float(loss_1d), rtol=2e-4, atol=2e-4
    )
    # one more step runs on the updated (still sharded) state
    _, _, loss2 = step_sp(p2, o2, batch)
    assert float(loss2) < float(loss_sp)


# The Pallas kernel in interpret mode. A case is (batch x heads, q_len,
# k_len, key width, value width, block_q, block_k, resident): blocks None
# are the rule's (``_block_sizes``), ``resident`` overrides ``_MAX_RESIDENT``
# so that a short sequence spans several grid blocks, as a long one does on
# the chip. Several blocks of equal lengths are walked by their kind (whole,
# diagonal, dead: straight-line code), of differing lengths in loops.
_PALLAS_CASES = {
    "tiles_2x2": (2, 32, 32, 8, 8, 16, 16, None),
    "tiles_4x4_two_heads": (2, 64, 64, 8, 8, 16, 16, None),
    "one_tile": (3, 32, 32, 8, 8, None, None, None),
    "wide_key_tiles": (2, 64, 64, 16, 16, 16, 32, None),
    "wide_query_tiles": (2, 64, 64, 16, 16, 32, 16, None),
    "cross_lengths_16_of_64": (2, 16, 64, 8, 8, 16, 16, None),
    "grid_blocks_4x4": (2, 128, 128, 8, 8, 16, 16, 32),
    "grid_blocks_2x4_tiles_2x1": (1, 64, 128, 8, 8, 16, 32, 32),
    "cross_lengths_grid_blocks": (2, 32, 128, 8, 8, 16, 16, 32),
    "rule_384": (1, 384, 384, 16, 16, None, None, None),
    "rule_gpt2_1024_64": (2, 1024, 1024, 64, 64, None, None, None),
    "kinds_one_tile_narrow_values": (2, 128, 128, 24, 16, 32, 32, 32),
    "kinds_tiles_2x2_narrow_values": (2, 128, 128, 24, 16, 16, 16, 32),
    "kinds_tiles_4x2_grid_2x2": (1, 64, 64, 24, 16, 8, 16, 32),
}
# (forward, gradient): float32 elementwise (atol = rtol), as the tests this
# one merged held it; bfloat16 against the largest reference entry
_PALLAS_TOL = {jnp.float32: (2e-5, 1e-4), jnp.bfloat16: (2e-2, 3e-2)}
# bfloat16 where the dtype changes the program: the scale folded into q
# (head dim 16, 64) or not (8), several grid blocks, the rule's own tiles
_PALLAS_BF16 = ("tiles_2x2", "wide_key_tiles", "cross_lengths_16_of_64",
                "grid_blocks_4x4", "rule_gpt2_1024_64",
                "kinds_tiles_2x2_narrow_values")
_PALLAS_PARAMS = [
    pytest.param(case, causal, dtype,
                 id=f"{case}-{'causal' if causal else 'full'}-{dtype.__name__}")
    for dtype, cases in ((jnp.float32, _PALLAS_CASES),
                         (jnp.bfloat16, _PALLAS_BF16))
    for case in cases for causal in (False, True)]


@pytest.mark.parametrize("case,causal,dtype", _PALLAS_PARAMS)
def test_flash_pallas_matches_reference(monkeypatch, case, causal, dtype):
    """Forward, and the gradients of q, k and v under a non-uniform
    cotangent (a uniform .sum() can mask a wrong delta = rowsum(dO*O)),
    against ``attention_reference`` in float32 on the same inputs. The
    kernel's matmuls take the inputs' dtype, so the tolerance follows it:
    float32 is held entry by entry (a wrong small entry, such as a masked
    row's, shows), bfloat16 against the largest reference entry."""
    from ray_tpu.ops import flash_kernels

    bh, q_len, k_len, d, d_v, block_q, block_k, resident = _PALLAS_CASES[case]
    if resident:
        monkeypatch.setattr(flash_kernels, "_MAX_RESIDENT", resident)
        jax.clear_caches()  # flash_attention is jitted: the rule is read
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    q = jax.random.normal(ks[0], (bh, q_len, d), dtype)
    k = jax.random.normal(ks[1], (bh, k_len, d), dtype)
    v = jax.random.normal(ks[2], (bh, k_len, d_v), dtype)
    w = jax.random.normal(ks[3], (bh, q_len, d_v), jnp.float32)
    f32 = lambda x: x.astype(jnp.float32)

    def pallas(q, k, v):
        return f32(flash_attention(q, k, v, causal=causal, block_q=block_q,
                                   block_k=block_k, impl="pallas_interpret"))

    def ref(q, k, v):
        return attention_reference(q, k, v, causal=causal)

    def out_and_grads(fn, *args):  # one compile a side
        out, vjp = jax.vjp(fn, *args)
        return out, vjp(w)

    def close(got, want, tol):
        if dtype == jnp.float32:
            np.testing.assert_allclose(got, want, atol=tol, rtol=tol)
        else:
            err = float(jnp.abs(f32(got) - want).max() / jnp.abs(want).max())
            assert err <= tol, err

    try:
        fwd_tol, grad_tol = _PALLAS_TOL[dtype]
        out, grads = jax.jit(functools.partial(out_and_grads, pallas))(q, k, v)
        want, want_grads = jax.jit(functools.partial(out_and_grads, ref))(
            f32(q), f32(k), f32(v))
        close(out, want, fwd_tol)
        for got, want in zip(grads, want_grads):
            assert got.dtype == dtype
            close(got, want, grad_tol)
    finally:
        if resident:
            jax.clear_caches()


def _all_looped(nq, nk, res_q, res_k, offset, causal, window=None):
    return {"whole": 0, "diagonal": 0, "trailing": 0, "dead": 0,
            "looped": nq * nk}


@pytest.mark.parametrize("case", ["kinds_tiles_2x2_narrow_values",
                                  "kinds_tiles_4x2_grid_2x2",
                                  "grid_blocks_4x4"])
def test_flash_walk_by_kind_agrees_with_the_loop_walk(monkeypatch, case):
    """One algorithm: the grid blocks walked by their kind and the same
    blocks walked in loops with traced bounds (the fallback, forced on the
    same inputs) give the same forward and the same three gradients in
    float32."""
    from ray_tpu.ops import flash_kernels

    bh, q_len, k_len, d, d_v, block_q, block_k, resident = _PALLAS_CASES[case]
    monkeypatch.setattr(flash_kernels, "_MAX_RESIDENT", resident)
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    q = jax.random.normal(ks[0], (bh, q_len, d))
    k = jax.random.normal(ks[1], (bh, k_len, d))
    v = jax.random.normal(ks[2], (bh, k_len, d_v))
    w = jax.random.normal(ks[3], (bh, q_len, d_v))

    def out_and_grads(q, k, v):
        out, vjp = jax.vjp(lambda *x: flash_attention(
            *x, causal=True, block_q=block_q, block_k=block_k,
            impl="pallas_interpret"), q, k, v)
        return (out, *vjp(w))

    walks = {}
    try:
        for walk in ("by_kind", "looped"):
            if walk == "looped":
                monkeypatch.setattr(flash_kernels, "_grid_kinds",
                                    _all_looped)
            jax.clear_caches()  # flash_attention is jitted
            jaxpr = jax.make_jaxpr(out_and_grads)(q, k, v)
            assert bool(kernel_whiles(jaxpr)) == (walk == "looped")
            walks[walk] = jax.jit(out_and_grads)(q, k, v)
    finally:
        jax.clear_caches()
    for got, want in zip(walks["by_kind"], walks["looped"]):
        np.testing.assert_allclose(got, want, atol=1e-6, rtol=1e-6)


def test_flash_grid_block_kinds():
    """The counter and the kernels follow one rule. At 8192 tokens a head
    is 4 x 4 grid blocks, 6 whole, 4 on the diagonal, 6 dead, none walked
    in a loop, and the traced kernels hold no loop with a traced bound;
    lengths that differ keep the loops."""
    from ray_tpu._private import steptrace
    from ray_tpu.ops.flash_kernels import grid_block_kinds

    # every block of a head is a grid step: no window here (PR 66)
    kinds = lambda *n: dict(zip(("whole", "diagonal", "dead", "looped"), n),
                            steps=sum(n), dead_steps=n[2])
    for backward in (False, True):
        assert grid_block_kinds(8192, 8192, True,
                                backward=backward) == kinds(6, 4, 6, 0)
    assert grid_block_kinds(4096, 4096, True) == kinds(1, 2, 1, 0)
    assert grid_block_kinds(1024, 1024, True) == kinds(0, 1, 0, 0)
    assert grid_block_kinds(8192, 8192, False) == kinds(16, 0, 0, 0)
    assert grid_block_kinds(4096, 8192, False) == kinds(8, 0, 0, 0)
    # a prefix already seen: lengths differ, so every block keeps the loops
    assert grid_block_kinds(4096, 8192, True) == kinds(0, 0, 0, 8)
    # one block a head is walked in straight-line code whatever its offset
    assert grid_block_kinds(16, 64, True, 16, 16) == kinds(0, 1, 0, 0)

    def grad_jaxpr(q_len, k_len):
        q = jax.ShapeDtypeStruct((64, q_len, 192), jnp.bfloat16)
        k = jax.ShapeDtypeStruct((64, k_len, 192), jnp.bfloat16)
        v = jax.ShapeDtypeStruct((64, k_len, 128), jnp.bfloat16)
        return jax.make_jaxpr(jax.grad(lambda *x: flash_attention(
            *x, causal=True, impl="pallas").astype(jnp.float32).sum(),
            argnums=(0, 1, 2)))(q, k, v)

    steptrace.set_enabled(True)
    steptrace.reset()
    try:
        jax.clear_caches()  # the record is written where a call is traced
        square = grad_jaxpr(8192, 8192)
        records = [r for r in steptrace.snapshot()
                   if r["kind"] == "counters"]
    finally:
        steptrace.set_enabled(False)
    assert kernel_calls(square) == {"flash_fwd": 1, "flash_bwd": 1}
    assert not kernel_whiles(square)
    assert {r["name"] for r in records} == {"attn/grid_blocks"}
    assert {r["values"]["backward"] for r in records} == {0, 1}
    for r in records:
        assert r["values"] == {**kinds(6, 4, 6, 0), "trailing": 0,
                               "queries": 8192, "keys": 8192,
                               "backward": r["values"]["backward"],
                               "window": 0, "heads": 64, "kv_heads": 64,
                               "dq_partials": 0}
    assert len(kernel_whiles(grad_jaxpr(4096, 8192))) == 2 * (4 + 8)


def test_flash_block_rule():
    """Tiles are multiples of 128 or the whole length; a grid step holds
    the whole sequence up to ``_MAX_RESIDENT``."""
    from ray_tpu.ops.flash_kernels import (_BWD_TILES, _FWD_TILES,
                                           _block_sizes)

    fwd = lambda *a: _block_sizes(*a, _FWD_TILES)
    assert fwd(1024, 1024, None, None) == (512, 512, 1024, 1024)
    assert _block_sizes(1024, 1024, None, None, _BWD_TILES) == (
        256, 256, 1024, 1024)
    assert fwd(512, 512, None, None) == (512, 512, 512, 512)
    assert fwd(384, 384, None, None) == (384, 384, 384, 384)
    assert fwd(8, 8, None, None) == (8, 8, 8, 8)
    assert fwd(640, 1280, None, None) == (128, 256, 640, 1280)
    assert fwd(8192, 8192, None, None) == (512, 512, 2048, 2048)
    assert fwd(64, 64, 16, 16) == (16, 16, 64, 64)
    # under a window of 1,024 to under 2,048 keys that whole tiles fill and
    # that divides the lengths, a head of several blocks holds the window's
    # length a step (PR 62); any other window changes nothing
    under = lambda n, window: _block_sizes(n, n, None, None, _FWD_TILES,
                                           window)
    assert under(8192, 1024) == (512, 512, 1024, 1024)
    assert _block_sizes(8192, 8192, None, None, _BWD_TILES, 1024) == (
        256, 256, 1024, 1024)
    assert under(2048, 1024) == (512, 512, 2048, 2048)
    for window in (512, 1280, 1536, 2048, 4096):
        assert under(16384, window) == (512, 512, 2048, 2048), window
    with pytest.raises(AssertionError):
        fwd(96, 96, 64, 16)


@pytest.mark.parametrize("impl", ["scan", "pallas_interpret"])
def test_flash_runs_per_batch_shard_under_a_data_mesh(impl):
    """Under a mesh whose data axis splits the batch the kernel runs inside
    ``shard_map``, one batch shard a device, and gives what one device
    gives."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    q, k, v = _qkv(b=8, h=2, s=32, d=8)
    ref = attention_reference(q, k, v, causal=True)
    fn = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True, impl=impl, block_q=16, block_k=16))
    by_batch = NamedSharding(mesh, PartitionSpec("data"))
    out = fn(*(jax.device_put(x, by_batch) for x in (q, k, v)))
    assert out.sharding.spec[0] == "data"
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("platform", ["tpu", "cpu"])
def test_flash_dispatch_never_hides_a_failed_kernel(monkeypatch, platform):
    """``impl=None`` asks the platform: on "tpu" it is the Pallas kernel or
    the kernel's own exception — never a quiet drop to scan, which at real
    size does not even fit the chip — and on "cpu" it is scan."""
    from ray_tpu.ops import attention

    def broken_kernel(*args, **kwargs):
        raise RuntimeError("Mosaic refused the kernel")

    taken = []
    scan = attention._flash_scan
    monkeypatch.setattr(attention, "_flash_pallas_diff", broken_kernel)
    monkeypatch.setattr(
        attention, "_flash_scan",
        lambda *a, **kw: taken.append("scan") or scan(*a, **kw))
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    jax.clear_caches()  # flash_attention is jitted: drop earlier decisions
    try:
        q, k, v = _qkv(s=32)
        if platform == "tpu":
            with pytest.raises(RuntimeError, match="Mosaic refused"):
                flash_attention(q, k, v, causal=True)
            assert taken == []
        else:
            out = flash_attention(q, k, v, causal=True)
            np.testing.assert_allclose(
                out, attention_reference(q, k, v, causal=True),
                atol=2e-5, rtol=2e-5)
            assert taken == ["scan"]
    finally:
        jax.clear_caches()


def _gpt2_gradient(seq=128, **kw):
    """(the gradient of GPT-2's loss at the toy size, its parameters): the
    parameters from the ``xla`` twin, whose tree is the same."""
    from ray_tpu.models import gpt2

    config = gpt2.GPT2Config.small_test(**kw)
    _, params = gpt2.init_params(
        dataclasses.replace(config, attention="xla"), jax.random.PRNGKey(0))
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (2, seq + 1), 0, config.vocab_size)
    batch = {"input_ids": tokens[:, :-1], "labels": tokens[:, 1:]}
    return jax.grad(functools.partial(
        gpt2.loss_fn, model=gpt2.GPT2(config), batch=batch)), params


@pytest.mark.parametrize("what,forward", [
    ("gpt2", 1), ("gpt2_remat", 1), ("checkpoint_no_policy", 2)])
def test_the_kernels_names_keep_nothing_without_a_policy(
        monkeypatch, what, forward):
    """The forward rule names the kernel's output and log-sum-exp for
    ``ops.remat.remat_policy``. GPT-2 recomputes its blocks under that
    policy: the gradient runs the forward kernel once a block, recomputed
    or not, and the backward kernel once. Without a policy a name is the
    identity: ``jax.checkpoint`` round the kernel alone runs its forward
    twice."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jax.clear_caches()  # flash_attention is jitted: drop earlier decisions
    try:
        if what == "checkpoint_no_policy":
            blocks = 1
            q, k, v = _qkv(s=128)
            grad = jax.make_jaxpr(jax.grad(lambda q: jax.checkpoint(
                functools.partial(flash_attention, causal=True))(
                    q, k, v).sum()))(q)
        else:
            fn, params = _gpt2_gradient(
                attention="flash", remat=what == "gpt2_remat")
            blocks = sum(k.startswith("h_") for k in params)
            grad = jax.make_jaxpr(fn)(params)
    finally:
        jax.clear_caches()
    assert blocks and kernel_calls(grad) == {
        "flash_fwd": forward * blocks, "flash_bwd": blocks}


def test_gpt2_without_the_kernel_the_policy_keeps_nothing(monkeypatch):
    """``attention="xla"`` makes no such name: GPT-2's recomputed gradient
    lowers to the program that recomputes under no policy."""
    from ray_tpu.models import gpt2

    fn, params = _gpt2_gradient(attention="xla", remat=True)
    lowered = lambda: jax.jit(fn).lower(params).as_text()
    kept = lowered()
    monkeypatch.setattr(gpt2, "remat_policy", lambda: None)
    assert kept == lowered()


def test_gpt2_keeps_the_output_the_kernel_would_write_again(monkeypatch):
    """The kernel path (interpreted here): GPT-2's gradients with the
    blocks recomputed, their kernel outputs kept, are those without
    recomputation bit for bit."""
    from ray_tpu.ops import attention

    monkeypatch.setattr(attention, "flash_attention", functools.partial(
        attention.flash_attention, impl="pallas_interpret"))
    # float32, as the other family's twin: in bf16 a recomputed block's
    # fusions round elsewhere, kernel or no kernel
    kw = dict(seq=64, attention="flash", dtype=jnp.float32)
    plain, params = _gpt2_gradient(**kw)
    kept, _ = _gpt2_gradient(remat=True, **kw)
    plain, kept = jax.jit(plain)(params), jax.jit(kept)(params)
    assert all(np.asarray(g).any() for g in jax.tree.leaves(kept))
    for a, b in zip(jax.tree.leaves(kept), jax.tree.leaves(plain)):
        np.testing.assert_array_equal(a, b)
