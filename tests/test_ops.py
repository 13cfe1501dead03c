"""Attention kernel numerics (vs naive reference) on the virtual CPU mesh."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops import (
    attention_reference,
    flash_attention,
    ring_self_attention,
)


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


@pytest.mark.parametrize("causal", [False, True])
def test_flash_pallas_interpret_matches_reference(causal):
    q, k, v = _qkv(b=1, h=2, s=32, d=8)
    ref = attention_reference(q, k, v, causal=causal)
    out = flash_attention(
        q, k, v, causal=causal, impl="pallas_interpret",
        block_q=16, block_k=16,
    )
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


def test_flash_pallas_grad_matches_reference():
    """The Pallas path is differentiable end-to-end: forward saves the
    logsumexp and the backward runs real Pallas dq / dkv kernels."""
    q, k, v = _qkv(b=1, h=1, s=32, d=8)

    def loss_pallas(q, k, v):
        return flash_attention(q, k, v, causal=True, impl="pallas_interpret",
                               block_q=16, block_k=16).sum()

    def loss_ref(q, k, v):
        return attention_reference(q, k, v, causal=True).sum()

    g_p = jax.grad(loss_pallas)(q, k, v)
    g_r = jax.grad(loss_ref)(q, k, v)
    np.testing.assert_allclose(g_p, g_r, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_pallas_grad_nonuniform_cotangent(causal):
    """Non-uniform cotangents exercise the delta = rowsum(dO*O) term of the
    flash backward — a uniform .sum() cotangent can mask a wrong delta."""
    q, k, v = _qkv(b=1, h=2, s=64, d=8, seed=3)
    w = jax.random.normal(jax.random.PRNGKey(9), q.shape, q.dtype)

    def loss_pallas(q, k, v):
        return (flash_attention(q, k, v, causal=causal,
                                impl="pallas_interpret",
                                block_q=16, block_k=16) * w).sum()

    def loss_ref(q, k, v):
        return (attention_reference(q, k, v, causal=causal) * w).sum()

    g_p = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
    g_r = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gp, gr in zip(g_p, g_r):
        np.testing.assert_allclose(gp, gr, atol=2e-4, rtol=2e-4)


def test_flash_pallas_cross_lengths():
    """q_len != k_len (decode-style causal offset) with streamed KV blocks:
    the kv axis is a grid dimension, so K/V VMEM residency is one
    (block_k, d) tile regardless of sequence length."""
    b, h, d = 1, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(ks[0], (b, h, 16, d))
    k = jax.random.normal(ks[1], (b, h, 64, d))
    v = jax.random.normal(ks[2], (b, h, 64, d))
    for causal in (False, True):
        ref = attention_reference(q, k, v, causal=causal)
        out = flash_attention(q, k, v, causal=causal,
                              impl="pallas_interpret",
                              block_q=16, block_k=16)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def loss_pallas(q, k, v):
        return flash_attention(q, k, v, causal=True, impl="pallas_interpret",
                               block_q=16, block_k=16).sum()

    def loss_ref(q, k, v):
        return attention_reference(q, k, v, causal=True).sum()

    g_p = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
    g_r = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gp, gr in zip(g_p, g_r):
        np.testing.assert_allclose(gp, gr, atol=1e-4, rtol=1e-4)


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
