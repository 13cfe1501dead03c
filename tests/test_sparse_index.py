"""The learned selection (``ray_tpu/ops/sparse_index.py``) and the attention
under it (``ops.attention.normed_rotary_self_attention(..., selected=)``),
at small sizes on the CPU: the threshold and the set against ``lax.top_k``,
rows shorter than ``topk`` among them; the kernels (interpreted) against the
twin; the attention's twin and kernels against the dense masked softmax,
forward and gradients; the KL and its gradient; the mask's words; what a
recomputed block keeps; ``rope_table`` under ``mrope_section``."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.llama import rope_frequencies, rope_table
from ray_tpu.ops import flash_kernels, rotary, sparse_index
from ray_tpu.ops.attention import (attention_reference,
                                   normed_rotary_self_attention)
from ray_tpu.ops.remat import remat_policy
from tests.conftest import kernel_calls


def _indexer(seq, heads=16, width=16, batch=1, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(keys[0], (batch, seq, heads, width)),
            jax.random.normal(keys[1], (batch, seq, width)),
            0.3 * jax.random.normal(keys[2], (batch, seq, heads)))


def _dense_scores(q_idx, k_idx, w):
    return jnp.einsum("btjs,btj->bts", jax.nn.relu(
        jnp.einsum("btjd,bsd->btjs", q_idx, k_idx)), w)


def _whole_indexer(seq, batch=2, seed=3):
    """Operands whose scores are whole eighths, exact in float32 in whatever
    order they are summed, with every other key its neighbour's copy: a
    query's scores come in pairs, and an odd ``topk`` ties at the edge."""
    q_idx, k_idx, w = _indexer(seq, batch=batch, seed=seed)
    k_idx = jnp.round(2 * k_idx)[:, jnp.arange(seq) // 2 * 2]
    return jnp.round(2 * q_idx), k_idx, jnp.round(8 * w) / 8


@pytest.mark.parametrize("impl,seq,topk,tied", [
    ("jnp", 64, 16, False), ("jnp", 48, 100, False), ("jnp", 64, 15, True),
    ("pallas_interpret", 512, 40, False),
    ("pallas_interpret", 512, 600, False),
    ("pallas_interpret", 512, 41, True)])
def test_the_set_is_the_topk_highest_earlier_keys(impl, seq, topk, tied):
    """The unpacked bits against ``lax.top_k`` over the dense scores, rows
    shorter than ``topk`` (all of their keys) among them; under ties at the
    ``topk``-th, every key that reaches it; and the kernel's words are the
    twin's."""
    q_idx, k_idx, w = (_whole_indexer if tied else _indexer)(seq, batch=2)
    select = functools.partial(sparse_index.select, q_idx, k_idx, w, topk,
                               rows=16, block_q=128, block_k=256)
    chosen = select(impl=impl)
    assert chosen.mask.dtype == jnp.int32
    assert chosen.mask.shape == (2, -(-seq // 256) * 8, seq)
    earlier = np.tril(np.ones((seq, seq), bool))
    scores = np.where(earlier, np.asarray(_dense_scores(q_idx, k_idx, w)),
                      -np.inf)
    kth = np.sort(scores, axis=-1)[..., -min(topk, seq)]
    want = np.broadcast_to(earlier, scores.shape).copy()
    want[:, topk:] &= (scores >= kth[..., None])[:, topk:]
    if tied:
        # the edge is a pair of equal scores somewhere: both are kept
        assert want.sum(-1).max() > topk
    elif topk < seq:
        _, picked = jax.lax.top_k(scores, topk)
        full = np.zeros_like(want)
        np.put_along_axis(full, np.asarray(picked), True, axis=-1)
        np.testing.assert_array_equal(want[:, topk:], full[:, topk:])
        assert want.sum() == 2 * sparse_index.pairs_selected(seq, topk)
    dense = sparse_index.unpack(chosen.mask)
    assert dense.dtype == jnp.int8 and dense.shape == (2, seq, seq)
    mask = np.asarray(dense).swapaxes(1, 2) != 0    # queries major
    np.testing.assert_array_equal(mask, want)
    if impl != "jnp":
        np.testing.assert_array_equal(chosen.mask, select(impl="jnp").mask)
    # the threshold: the topk-th largest where a row has more keys, -inf
    # where it has fewer (with exactly topk either keeps them all)
    np.testing.assert_allclose(chosen.tau[:, topk:], kth[:, topk:], rtol=1e-5)
    assert np.all(np.asarray(chosen.tau)[:, :topk - 1] == -np.inf)
    np.testing.assert_allclose(chosen.lse, jax.nn.logsumexp(
        np.where(want, scores, -np.inf), axis=-1), rtol=1e-5)


def test_the_words_hold_a_key_where_a_register_finds_it():
    """``pack``'s layout: bit j of row 8 c + i is key 256 c + 8 j + i;
    ``bits`` and ``unpack`` give every pair back."""
    seen = jax.random.bernoulli(jax.random.PRNGKey(2), 0.3, (2, 512, 128))
    words = sparse_index.pack(seen)
    assert words.shape == (2, 16, 128) and words.dtype == jnp.int32
    c, j, i = np.meshgrid(np.arange(2), np.arange(32), np.arange(8),
                          indexing="ij")
    got = (np.asarray(words)[:, 8 * c + i] >> j[..., None]) & 1
    np.testing.assert_array_equal(
        got.reshape(2, 512, 128), np.asarray(seen, np.int32))
    for b in range(2):
        np.testing.assert_array_equal(sparse_index.bits(words[b]) != 0,
                                      seen[b])
    square = jnp.pad(seen, ((0, 0), (0, 0), (0, 384)))
    np.testing.assert_array_equal(
        sparse_index.unpack(sparse_index.pack(square)), square)


def _main(seq, heads=4, kv=2, d=16, batch=1):
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    return (jax.random.normal(keys[0], (batch * heads, seq, d)),
            jax.random.normal(keys[1], (batch * kv, seq, d)))


def _dense_kl(q_idx, k_idx, w, mask, qf, kf, scale):
    seen = jnp.swapaxes(mask, 1, 2) != 0
    b, seq = q_idx.shape[:2]
    qh = qf.reshape(b, kf.shape[0] // b, -1, seq, qf.shape[-1])
    kh = kf.reshape(b, -1, seq, kf.shape[-1])
    s = jnp.einsum("bgjtd,bgsd->bgjts", qh, kh) * scale
    p = jax.nn.softmax(jnp.where(seen[:, None, None], s, -jnp.inf), -1).mean(
        axis=(1, 2))
    log_q = jax.nn.log_softmax(
        jnp.where(seen, _dense_scores(q_idx, k_idx, w), -jnp.inf), -1)
    live = seen & (p > 0)
    return jnp.where(live, p * (jnp.log(jnp.where(live, p, 1.0))
                                - jnp.where(live, log_q, 0.0)), 0.0).sum()


@pytest.mark.parametrize("impl", ["jnp", "pallas_interpret"])
def test_the_kl_and_its_gradient(impl):
    seq, topk, scale = 512, 24, 16 ** -0.5
    q_idx, k_idx, w = _indexer(seq, seed=5)
    qf, kf = _main(seq)
    chosen = sparse_index.select(q_idx, k_idx, w, topk, impl="jnp")
    mask = sparse_index.unpack(chosen.mask)
    qh = qf.reshape(1, 2, 2, seq, 16)
    s = jnp.einsum("bgjtd,bgsd->bgjts", qh, kf[None]) * scale
    lse = jax.nn.logsumexp(jnp.where(
        jnp.swapaxes(mask, 1, 2)[:, None, None] != 0, s, -jnp.inf),
        -1).reshape(4, 1, seq)
    got = jax.value_and_grad(lambda *x: sparse_index.index_kl(
        *x, chosen, qf, kf, lse, topk=topk, sm_scale=scale, impl=impl,
        rows=64, tile=256), argnums=(0, 1, 2))(q_idx, k_idx, w)
    want = jax.value_and_grad(lambda *x: _dense_kl(
        *x, mask, qf, kf, scale), argnums=(0, 1, 2))(q_idx, k_idx, w)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for a, b in zip(got[1], want[1]):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=1e-4)
    # nothing reaches the main attention's operands
    to_main = jax.grad(lambda qf, kf: sparse_index.index_kl(
        q_idx, k_idx, w, chosen, qf, kf, lse, topk=topk, sm_scale=scale,
        impl=impl, rows=64, tile=256), argnums=(0, 1))(qf, kf)
    assert not any(float(jnp.abs(g).max()) for g in to_main)


def test_a_recomputed_block_keeps_the_kl_kernels_gradients():
    seq, topk = 512, 40
    q_idx, k_idx, w = _indexer(seq, seed=5)
    qf, kf = _main(seq)
    chosen = sparse_index.select(q_idx, k_idx, w, topk, impl="jnp")
    lse = jnp.zeros((4, 1, seq))
    kept = jax.checkpoint(lambda *x: sparse_index.index_kl(
        *x, chosen, qf, kf, lse, topk=topk, sm_scale=0.25,
        impl="pallas_interpret", tile=256), policy=remat_policy())
    jaxpr = jax.make_jaxpr(jax.grad(kept, argnums=(0, 1, 2)))(
        q_idx, k_idx, w)
    assert kernel_calls(jaxpr) == {"index_kl": 1}    # not run a second time
    text = str(jaxpr)
    kept = [name for name in sparse_index.REMAT_NAMES
            if name.startswith("index_kl")]
    assert len(kept) == 3 and all(f"name={name}" in text for name in kept)


def test_a_recomputed_block_keeps_the_selections_bits(monkeypatch):
    """A block as the model's layer has it (the selection, the attention
    under it, the indexer's loss), recomputed under ``remat_policy()``: its
    gradient runs each of the four kernels ONCE. The flash backward finds
    the mask's words among the kept values, so the recomputation makes
    neither the index scores nor the thresholds a second time."""
    seq, topk, d = 1024, 40, 128
    monkeypatch.setattr(flash_kernels, "_MAX_RESIDENT", 512)
    jax.clear_caches()
    q, k, v, scales, _ = _layer(seq)
    q_idx, k_idx, w = _indexer(seq, seed=9)

    def block(q, k, v, q_scale, k_scale, q_idx, k_idx, w):
        chosen = sparse_index.select(q_idx, k_idx, w, topk,
                                     impl="pallas_interpret")
        y, (qf, kf, lse) = normed_rotary_self_attention(
            q, k, v, q_scale, k_scale, None, None, eps=1e-6,
            attention="flash", impl="pallas_interpret", block_q=256,
            block_k=256, selected=chosen.mask, topk=topk)
        return y.sum() + sparse_index.index_kl(
            q_idx, k_idx, w, chosen, qf, kf, lse, topk=topk,
            sm_scale=d ** -0.5, impl="pallas_interpret", tile=256)

    try:
        jaxpr = jax.make_jaxpr(jax.grad(
            jax.checkpoint(block, policy=remat_policy()),
            argnums=tuple(range(8))))(q, k, v, *scales, q_idx, k_idx, w)
    finally:
        jax.clear_caches()
    calls = kernel_calls(jaxpr)
    ours = (f"index_select_top{topk}", "index_kl", f"flash_fwd_sel{topk}",
            f"flash_bwd_sel{topk}")
    assert {name: calls[name] for name in ours} == dict.fromkeys(ours, 1)
    assert "index_mask" in sparse_index.REMAT_NAMES
    text = str(jaxpr)
    for name in sparse_index.REMAT_NAMES:
        assert f"name={name}" in text
    # what crosses into the backward pass of the mask is its words: nothing
    # a byte a pair, nothing a score a pair
    assert f"i32[1,{seq // 32},{seq}]" in text
    assert f"i8[1,{seq},{seq}]" not in text
    assert f"f32[1,{seq},{seq}]" not in text


def _layer(seq, heads=2, kv=1, d=128, seed=1):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(keys[0], (1, seq, heads, d))
    k = jax.random.normal(keys[1], (1, seq, kv, d))
    v = jax.random.normal(keys[2], (1, seq, kv, d))
    scales = 1.0 + 0.1 * jax.random.normal(keys[3], (2, d))
    return q, k, v, scales, jax.random.normal(keys[4], (1, seq, heads, d))


def _out_and_grads(call, weights, *operands):
    def scalar(*operands):
        out = call(*operands)[0]
        return (out * weights).sum(), out
    grads, out = jax.grad(scalar, argnums=tuple(range(len(operands))),
                          has_aux=True)(*operands)
    return (out, *grads)


def test_the_attention_under_a_selection(monkeypatch):
    """Twin and kernels (interpreted, PR 63's prologue pair with them),
    both on the selection's packed words (residents of 512 keys in tiles of
    256: a chunk of words a tile), against the dense masked softmax of the
    normed, rotated heads: output, the gradients of q, k, v and both
    scales, and what the call hands the indexer's loss; a recomputed block
    keeps the flash kernel's output."""
    seq, topk, d = 1024, 100, 128
    monkeypatch.setattr(flash_kernels, "_MAX_RESIDENT", 512)
    jax.clear_caches()
    q, k, v, scales, weights = _layer(seq)
    q_idx, k_idx, w = _indexer(seq, seed=9)
    words = sparse_index.select(q_idx, k_idx, w, topk, impl="jnp").mask
    mask = sparse_index.unpack(words)
    triples = jnp.stack([jnp.arange(seq), jnp.arange(seq) // 3,
                         jnp.arange(seq) % 5])[:, None]
    cos, sin = rope_table(d, triples, {
        "rope_type": "default", "rope_theta": 10000.0,
        "mrope_section": (16, 24, 24)})
    assert rotary.fits(q, cos)      # three rows, still one [1, T, 64] table

    def call(impl, path, q, k, v, q_scale, k_scale):
        return normed_rotary_self_attention(
            q, k, v, q_scale, k_scale, cos, sin, eps=1e-6, attention=path,
            impl=impl, block_q=256, block_k=256, selected=words,
            topk=topk)

    def dense(q, k, v, q_scale, k_scale):
        turn = functools.partial(rotary.head_rotary, cos=cos, sin=sin,
                                 eps=1e-6)
        bhsd = lambda t: t.transpose(0, 2, 1, 3)
        return (attention_reference(
            bhsd(turn(q, q_scale)), bhsd(turn(k, k_scale)), bhsd(v),
            causal=True, selected=mask).transpose(0, 2, 1, 3),)

    try:
        want = _out_and_grads(dense, weights, q, k, v, *scales)
        twin = _out_and_grads(functools.partial(call, "jnp", "xla"), weights,
                              q, k, v, *scales)
        got = jax.jit(functools.partial(
            _out_and_grads, functools.partial(
                call, "pallas_interpret", "flash"), weights))(
                    q, k, v, *scales)
        _, (qf, kf, lse) = call("pallas_interpret", "flash", q, k, v, *scales)
        kept = jax.checkpoint(
            lambda *x: call("pallas_interpret", "flash", *x)[0].sum(),
            policy=remat_policy())
        calls = kernel_calls(jax.make_jaxpr(jax.grad(kept))(
            q, k, v, *scales))
    finally:
        jax.clear_caches()
    for a, b, c in zip(got, twin, want):
        np.testing.assert_allclose(b, c, atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(a, c, atol=5e-5, rtol=5e-5)
    s = jnp.einsum("htd,hsd->hts", qf, jnp.repeat(kf, 2, axis=0)) * d ** -0.5
    s = jnp.where(jnp.swapaxes(mask, 1, 2) != 0, s, -jnp.inf)
    np.testing.assert_allclose(lse[:, 0], jax.nn.logsumexp(s, -1), rtol=1e-5)
    # one forward, one backward: the forward is not run again
    assert calls[f"flash_fwd_sel{topk}"] == calls[f"flash_bwd_sel{topk}"] == 1


def test_the_table_of_three_position_rows():
    """Pair i reads the row its section names, in order; three equal rows
    give the plain table to the bit; what does not add up is refused."""
    d, theta, section = 16, 10000.0, (2, 3, 3)
    entry = {"rope_type": "default", "rope_theta": theta,
             "mrope_section": section}
    rows = jnp.stack([jnp.arange(12), 2 * jnp.arange(12) + 1,
                      jnp.arange(12) % 4])[:, None]            # [3, 1, 12]
    cos, sin = rope_table(d, rows, entry)
    assert cos.shape == sin.shape == (1, 12, 8)
    inv = theta ** (-2.0 * np.arange(8) / d)
    angle = np.asarray(rows, np.float64)[np.repeat(np.arange(3), section),
                                         0].T * inv            # [12, 8]
    np.testing.assert_allclose(cos[0], np.cos(angle), atol=1e-6)
    np.testing.assert_allclose(sin[0], np.sin(angle), atol=1e-6)
    same = jnp.broadcast_to(jnp.arange(12), (3, 1, 12))
    plain = rope_frequencies(d, same[0], theta)
    for got, want in zip(rope_table(d, same, entry), plain):
        np.testing.assert_array_equal(got, want)
    with pytest.raises(ValueError):
        rope_table(d, rows, dict(entry, mrope_section=(2, 3, 4)))
    with pytest.raises(ValueError):
        rope_table(d, rows[:2], entry)
