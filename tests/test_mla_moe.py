"""The latent-attention, routed-expert model (``ray_tpu.models.mla_moe``) and
what it stands on (``ops.moe.topk_routing`` / ``held_expert_ffn``, the flash
kernel at a key width and a value width of their own, ``ops.xent``), held to
the plain reference ``perfbench/families/mla_moe_reference.py`` at small
sizes on the CPU, seeded weights, no cluster."""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import compare, worker
from ray_tpu._private import steptrace
from ray_tpu.models import gpt2, mla_moe
from ray_tpu.ops import attention, flash_kernels, moe, mosaic, xent
from tests.conftest import kernel_calls

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "perfbench", "tests", "configs",
                       "tiny-mla-moe.json")) as _f:
    TOY = json.load(_f)
REFERENCE = worker.load_reference(ROOT, TOY)
FAMILY = worker.load_family(ROOT, TOY)
TRAFFIC = {"batch": 4, "seq": 64, "remat": True}


def _tokens(seed, vocab=TOY["vocab_size"], batch=4, seq=64):
    return np.random.default_rng(seed).integers(
        0, vocab, (batch, seq + 1), dtype=np.int32)


def _differences(dtype, seed=3, round_weights=False):
    """The comparison the benchmark's worker makes, in small: the step's
    loss and its gradient (from Adam's first moment) against the float32
    reference -> (loss, gradient norm: relative; cosine)."""
    model = dict(TOY, train=dict(TOY["train"], compute_dtype=dtype))
    built = FAMILY.build(model, TRAFFIC, None)
    params, opt_state = jax.jit(built.make_state)(jax.random.PRNGKey(seed))
    tokens = _tokens(seed)
    ref_loss, ref_grads = REFERENCE.over_microbatches(
        model, params, tokens, 2, True, jnp.asarray)
    if round_weights:
        # the control: weights kept to 3 bits of mantissa (what fp8 e4m3
        # holds), as perfbench/tests/test_yardstick.py does for GPT-2
        def chop(x):
            if x.ndim < 2:
                return x
            m, e = jnp.frexp(x)
            return jnp.ldexp(jnp.round(m * 16) / 16, e)

        params = jax.tree.map(chop, params)
    batch = {"input_ids": jnp.asarray(tokens[:, :-1]),
             "labels": jnp.asarray(tokens[:, 1:])}
    _, opt_state, loss = built.step(params, opt_state, batch)
    ns, nr, cos = (float(v) for v in compare.compare_gradients(
        compare.system_gradient(opt_state, 0.9), ref_grads))
    return (abs(float(loss) - float(ref_loss)) / float(ref_loss),
            abs(ns - nr) / nr, cos)


def test_float32_step_is_the_reference_to_rounding():
    d_loss, d_norm, cos = _differences("float32")
    assert d_loss <= 1e-5 and d_norm <= 1e-4 and cos >= 0.99999, (
        d_loss, d_norm, cos)


# bfloat16 against float32 at the toy size, seeds 0-7 read on the CPU: loss
# 1.3e-7 to 1.9e-5, gradient norm 1.5e-4 to 1.1e-3, cosine 0.99989 to
# 0.99997. The control (weights kept to 3 bits of mantissa, seeds 3 and 5):
# loss 2.6e-5 and 1.0e-4, norm 1.0e-3 and 2.5e-3, cosine 0.9982: it is the
# cosine that tells them apart, so its limit lies between the two readings
# (1 - cosine: 1.1e-4 against 1.8e-3, limit 5e-4); loss and norm at 5x the
# worst reading.
BF16_LIMITS = {"loss": 1e-4, "norm": 5e-3, "cosine": 0.9995}


@pytest.mark.parametrize("seed", [3, 7])
def test_bfloat16_step_is_inside_the_toy_limits(seed):
    d_loss, d_norm, cos = _differences("bfloat16", seed)
    assert d_loss <= BF16_LIMITS["loss"], d_loss
    assert d_norm <= BF16_LIMITS["norm"], d_norm
    assert cos >= BF16_LIMITS["cosine"], cos


def test_a_step_in_a_lower_precision_is_outside_them():
    d_loss, d_norm, cos = _differences("bfloat16", round_weights=True)
    assert (d_loss > BF16_LIMITS["loss"] or d_norm > BF16_LIMITS["norm"]
            or cos < BF16_LIMITS["cosine"]), (d_loss, d_norm, cos)


# ----------------------------------------------------------------------
# the expert layer
# ----------------------------------------------------------------------

def _layer(seed=0, T=96, d=32, width=16, experts=8, k=3):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    return {
        "x": jax.random.normal(keys[0], (T, d)),
        "router": jax.random.normal(keys[1], (d, experts)) * 0.3,
        "bias": jnp.zeros((experts,)),
        "wi": jax.random.normal(keys[2], (experts, d, 2 * width)) * 0.2,
        "wo": jax.random.normal(keys[3], (experts, width, d)) * 0.2,
        "k": k,
    }


def _held_part(layer, index, of, bias=None):
    held = layer["wi"].shape[0] // of
    experts, weights = moe.topk_routing(
        layer["x"], layer["router"],
        layer["bias"] if bias is None else bias, layer["k"], 2.5)
    rows = slice(index * held, (index + 1) * held)
    return moe.held_expert_ffn(layer["x"], experts, weights,
                               layer["wi"][rows], layer["wo"][rows],
                               index=index, of=of)


def _reference_layer(layer, index, of, shared, bias=None):
    """The reference's expert layer (routed part of shard ``index`` of
    ``of`` plus the shared expert) on ``layer``'s weights."""
    held = layer["wi"].shape[0] // of
    rows = slice(index * held, (index + 1) * held)
    p = {"router": layer["router"],
         "router_bias": layer["bias"] if bias is None else bias,
         "experts_wi": layer["wi"][rows], "experts_wo": layer["wo"][rows],
         "shared_experts": shared}
    m = {"expert_shard": {"index": index, "of": of},
         "num_experts_per_tok": layer["k"], "norm_topk_prob": True,
         "routed_scaling_factor": 2.5}
    with jax.default_matmul_precision("highest"):
        return REFERENCE._experts(layer["x"][None], p, m)[0]


def _shared(d=32, width=16, seed=9):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return {"gate_proj": {"kernel": jax.random.normal(keys[0], (d, width))},
            "up_proj": {"kernel": jax.random.normal(keys[1], (d, width))},
            "down_proj": {"kernel": jax.random.normal(keys[2], (width, d))}}


@pytest.mark.parametrize("of", [2, 4, 8])
def test_the_shares_add_up_to_the_uncut_layer(of):
    """The routed parts of all ``of`` shares, with the shared expert counted
    once, are the uncut reference layer."""
    layer, shared = _layer(), _shared()
    parts = [_held_part(layer, i, of) for i in range(of)]
    routed = sum(y for y, _ in parts)
    with jax.default_matmul_precision("highest"):
        once = REFERENCE._swiglu(layer["x"], shared)
    whole = _reference_layer(layer, 0, 1, shared)
    np.testing.assert_allclose(routed + once, whole, rtol=2e-4, atol=2e-5)
    # every pair fell on exactly one share
    assert sum(int(n.sum()) for _, n in parts) == 96 * layer["k"]
    # and each share is the reference's share
    for i in (0, of - 1):
        np.testing.assert_allclose(
            parts[i][0] + once, _reference_layer(layer, i, of, shared),
            rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("forced,pairs", [("all", 96 * 3), ("none", 0)])
def test_no_pair_of_a_held_expert_is_dropped(forced, pairs):
    """A router forced to send every token's every pair to the held experts
    (the row buffer full), and one that sends none: the layer computes
    exactly the pairs that fall on it."""
    layer, of, index = _layer(seed=2), 2, 1        # holds experts 4..7
    push = 10.0 if forced == "all" else -10.0
    bias = jnp.where(jnp.arange(8) >= 4, push, 0.0)
    y, tokens = _held_part(layer, index, of, bias)
    assert int(tokens.sum()) == pairs
    want = _reference_layer(layer, index, of, _shared(), bias)
    with jax.default_matmul_precision("highest"):
        want = want - REFERENCE._swiglu(layer["x"], _shared())
    np.testing.assert_allclose(y, want, rtol=2e-4, atol=2e-5)
    if forced == "none":
        assert not np.asarray(y).any()


def _each_pair_by_itself(x, experts, weights, wi, wo, *, index, of):
    """``held_expert_ffn`` with no buffer, no sort and no loop: every pair
    looks its expert's matrices up, and autodiff does the rest."""
    held = wi.shape[0]
    local = experts - index * held
    mine = (local >= 0) & (local < held)
    expert = jnp.clip(local, 0, held - 1)
    with jax.default_matmul_precision("highest"):
        gate, up = jnp.split(
            jnp.einsum("td,tkdh->tkh", x, wi[expert]), 2, axis=-1)
        out = jnp.einsum("tkh,tkhd->tkd", jax.nn.silu(gate) * up, wo[expert])
    y = jnp.where(mine, weights, 0.0)[..., None] * out
    tokens = (jnp.where(mine, local, held)[..., None]
              == jnp.arange(held)).sum(axis=(0, 1))
    return y.sum(axis=1), tokens


def _unwritten_past_the_groups(plain, traced):
    """``jax.lax.ragged_dot`` as a TPU runs it: a group's matmul reads its
    own rows only, and the rows past the last group, of the result and of
    the operand's cotangent, hold whatever was there: here NaN. Each trace
    of it leaves a mark in ``traced``."""
    def live(lhs, sizes):
        return (jnp.arange(lhs.shape[0]) < sizes.sum())[:, None]

    @jax.custom_vjp
    def ragged_dot(lhs, rhs, sizes):
        traced.append(lhs.shape)
        out = plain(jnp.where(live(lhs, sizes), lhs, 0.0), rhs, sizes)
        return jnp.where(live(lhs, sizes), out, jnp.nan)

    def backward(res, g):
        lhs, rhs, sizes = res
        keep = live(lhs, sizes)
        d_lhs, d_rhs = jax.vjp(
            lambda lhs, rhs: plain(lhs, rhs, sizes),
            jnp.where(keep, lhs, 0.0), rhs)[1](jnp.where(keep, g, 0.0))
        return jnp.where(keep, d_lhs, jnp.nan), d_rhs, None

    ragged_dot.defvjp(lambda *a: (ragged_dot(*a), a), backward)
    return ragged_dot


_PAIRS = 96 * 3
_SOURCES = (80, 160, _PAIRS)     # where the test lets a fast gather end
_PRESENT = sorted({0, 1, _PAIRS} | {n + more for more in (0, 1) for n in (
    _SOURCES[:-1] + moe.row_buffer_rungs(_PAIRS)[:-1][::8])})


@pytest.mark.parametrize("present", _PRESENT)
def test_every_rung_is_the_one_length_computation(present, monkeypatch):
    """Pairs present from none, through the ends of some of the walk's
    chunks and of each source the gathers back to the tokens are cut to,
    and one more, to all tokens x k: the layer's result, its counts and
    its four gradients are those of each pair computed by itself, and stay
    so when the grouped matmul leaves rows past its groups unwritten."""
    layer = _layer(seed=present)
    width = layer["x"].shape[1] * layer["x"].dtype.itemsize
    monkeypatch.setattr(moe, "_FAST_GATHER_SOURCE_BYTES",
                        _SOURCES[0] // 2 * width)
    assert moe._gather_sources(jnp.zeros((_PAIRS, width // 4))) == _SOURCES
    T, k, held, of = 96, layer["k"], 4, 2
    rng = np.random.default_rng(present)
    on_held = np.zeros(_PAIRS, bool)
    on_held[rng.permutation(_PAIRS)[:present]] = True
    experts = jnp.asarray(np.where(
        on_held, rng.integers(held, 2 * held, _PAIRS),
        rng.integers(0, held, _PAIRS)).reshape(T, k).astype(np.int32))
    weights = jax.random.uniform(jax.random.PRNGKey(present), (T, k),
                                 minval=0.1)
    target = jax.random.normal(jax.random.PRNGKey(1), layer["x"].shape)

    def through(ffn):
        def loss(x, weights, wi, wo):
            y, tokens = ffn(x, experts, weights, wi, wo, index=1, of=of)
            return (y * target).sum(), (y, tokens)
        return jax.value_and_grad(loss, argnums=(0, 1, 2, 3), has_aux=True)(
            layer["x"], weights, layer["wi"][held:], layer["wo"][held:])

    (_, (want_y, want_n)), want = through(_each_pair_by_itself)
    assert int(want_n.sum()) == present
    lengths = moe.row_buffer_rungs(_PAIRS)
    assert lengths[int(moe.row_buffer_rung(present, _PAIRS))] >= present
    traced = []
    for unwritten in (False, True):
        if unwritten:
            jax.clear_caches()         # the layer's passes are jitted
            monkeypatch.setattr(
                jax.lax, "ragged_dot",
                _unwritten_past_the_groups(jax.lax.ragged_dot, traced))
        (_, (y, n)), got = through(moe.held_expert_ffn)
        assert np.array_equal(n, want_n)
        # sums in the order of the sort and a chunk at a time, not in the
        # tokens' order: float32's last digits
        for a, b in zip((y,) + got, (want_y,) + want):
            assert np.isfinite(np.asarray(a)).all()
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
    assert traced                      # the second pass ran the stand-in
    monkeypatch.undo()
    jax.clear_caches()


@pytest.mark.parametrize("pairs", [5, 64, _PAIRS, 16384 * 8])
def test_the_rungs_rise_to_the_pairs_and_hold_what_is_present(pairs):
    rungs = moe.row_buffer_rungs(pairs)
    assert list(rungs) == sorted(set(rungs)) and rungs[-1] == pairs
    # whole chunks of a thirty-second (in tiles of 8 rows), then the whole
    chunk = -(-pairs // 256) * 8
    assert len(rungs) <= 32 and rungs[:-1] == tuple(
        chunk * (i + 1) for i in range(len(rungs) - 1))
    assert rungs[-1] - chunk < pairs <= len(rungs) * chunk
    present = np.arange(0, pairs + 1, max(1, pairs // 4096))
    index = np.asarray(moe.row_buffer_rung(present, pairs))
    assert (np.diff(index) >= 0).all() and index[-1] == len(rungs) - 1
    held = np.asarray(rungs)[index]
    assert (held >= present).all()
    # the first rung that holds them, not a later one
    assert ((index == 0) | (np.asarray(rungs)[index - 1] < present)).all()
    # derived from the pairs: another size, another ladder
    assert moe.row_buffer_rungs(2 * pairs) != rungs
    # a traced count chooses as a number does
    assert int(jax.jit(lambda n: moe.row_buffer_rung(n, pairs))(
        jnp.int32(pairs // 3))) == moe.row_buffer_rung(pairs // 3, pairs)


def _routing_of(case, T, k, held, of):
    """(T, k) experts of a layer that holds experts 0 .. held - 1 of held x
    of: ``case`` pairs at random places on held experts, uniformly, or
    "uneven": a third of all pairs, nine in ten of them on one expert and
    none on another."""
    rng = np.random.default_rng(7)
    present = T * k // 3 if case == "uneven" else case
    on_held = np.zeros(T * k, bool)
    on_held[rng.permutation(T * k)[:present]] = True
    to = rng.integers(0, held, T * k)
    if case == "uneven":
        to = np.where(rng.random(T * k) < 0.9, 1, rng.integers(2, held, T * k))
    return present, jnp.asarray(np.where(
        on_held, to, rng.integers(held, held * of, T * k)
    ).reshape(T, k).astype(np.int32))


@pytest.mark.parametrize(
    "case", [0, 1, 4096, 4097, "uneven", 16384 * 8],
    ids=["none", "one", "a_chunks_edge", "one_past_the_edge", "uneven",
         "all"])
def test_nobody_reads_what_the_walk_did_not_write(case, monkeypatch):
    """At the cells' tokens x k (131,072 pairs, chunks of 4,096 rows): the
    layer's result, its counts and its four gradients with every walked
    buffer started from NaN, as a TPU's unwritten memory may hold, and the
    grouped matmul leaving rows past its groups NaN too, are those of the
    buffers started from zeros, to the bit: a walked chunk is written
    whole, and what lies beyond reaches no matmul's group and no token."""
    T, k, d, width, held, of = 16384, 8, 16, 8, 4, 4
    present, experts = _routing_of(case, T, k, held, of)
    keys = jax.random.split(jax.random.PRNGKey(3), 5)
    x, target = (jax.random.normal(key, (T, d)) for key in keys[:2])
    weights = jax.random.uniform(keys[2], (T, k), minval=0.1)
    wi = jax.random.normal(keys[3], (held, d, 2 * width)) * 0.2
    wo = jax.random.normal(keys[4], (held, width, d)) * 0.2

    def both_ways():
        def loss(x, weights, wi, wo):
            y, tokens = moe.held_expert_ffn(x, experts, weights, wi, wo,
                                            index=0, of=of)
            return (y * target).sum(), (y, tokens)
        (_, aux), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3), has_aux=True)(x, weights, wi, wo)
        return aux + grads

    assert moe._fresh_buffer(x) is jnp.zeros
    want = both_ways()
    assert int(want[1].sum()) == present
    started, traced = [], []

    def nan_filled(shape, dtype):
        started.append(shape)
        return jnp.full(shape, jnp.nan, dtype)

    jax.clear_caches()                 # the layer's passes are jitted
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(moe, "_unwritten", nan_filled)
    monkeypatch.setattr(
        jax.lax, "ragged_dot",
        _unwritten_past_the_groups(jax.lax.ragged_dot, traced))
    got = both_ways()
    monkeypatch.undo()
    jax.clear_caches()
    # two buffers forward; five backward, the float32 vector among them
    rows = T * k
    assert started == [(rows, d), (rows, width), (rows, d), (rows, d),
                       (rows, 2 * width), (rows, width), (rows,)]
    assert traced
    for a, b in zip(got, want):
        assert np.isfinite(np.asarray(a)).all()
        assert np.array_equal(a, b)


@pytest.mark.parametrize(
    "case", [0, 1, 300, "uneven", 128 * 4],
    ids=["none", "one", "some", "uneven", "all"])
def test_the_layer_under_the_grouped_matmul_kernels_is_each_pair_by_itself(
        case, monkeypatch):
    """``held_expert_ffn`` where a TPU under no mesh takes the kernels of
    ``ops/grouped_matmul.py`` (the backend's name and the device's kind
    stood in for, the kernels interpreted, every walked buffer started from NaN): the result, the
    counts and the four gradients are those of each pair computed by
    itself; each of the seven grouped matmuls wrote one
    ``moe/grouped_matmul`` record that says ``kernel``; ``ragged_dot`` is
    not traced."""
    from ray_tpu.ops import grouped_matmul
    T, k, d, width, held, of = 128, 4, 192, 128, 4, 2
    present, experts = _routing_of(case, T, k, held, of)
    keys = jax.random.split(jax.random.PRNGKey(5), 5)
    x, target = (jax.random.normal(key, (T, d)) for key in keys[:2])
    weights = jax.random.uniform(keys[2], (T, k), minval=0.1)
    wi = jax.random.normal(keys[3], (held, d, 2 * width)) * 0.1
    wo = jax.random.normal(keys[4], (held, width, d)) * 0.1

    def through(ffn):
        def loss(x, weights, wi, wo):
            y, tokens = ffn(x, experts, weights, wi, wo, index=0, of=of)
            return (y * target).sum(), (y, tokens)
        (_, aux), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3), has_aux=True)(x, weights, wi, wo)
        return aux + grads

    want = through(_each_pair_by_itself)
    assert int(want[1].sum()) == present
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(mosaic, "device_kind", lambda: "TPU v5 lite")
    monkeypatch.setattr(moe, "_unwritten",
                        lambda shape, dtype: jnp.full(shape, jnp.nan, dtype))
    monkeypatch.setattr(
        jax.lax, "ragged_dot", lambda *a, **kw: pytest.fail("ragged_dot"))
    for name in ("by_group", "per_group"):
        monkeypatch.setattr(grouped_matmul, name, functools.partial(
            getattr(grouped_matmul, name), interpret=True))
    steptrace.set_enabled(True)
    steptrace.reset()
    try:
        jax.clear_caches()             # the layer's passes are jitted
        got = through(moe.held_expert_ffn)
        drawn = [e["args"] for e in steptrace.chrome_trace(
            steptrace.merge_records(steptrace.snapshot()))
            if e["ph"] == "C" and e["name"] == "moe/grouped_matmul"]
    finally:
        steptrace.set_enabled(False)
    monkeypatch.undo()
    jax.clear_caches()
    rows = T * k
    tile = grouped_matmul._ROWS_A_TILE
    record = {"kernel": 1, "rows": rows, "held": held, "tile": tile}
    assert drawn == [dict(record, form=form, k=k_, n=n, block_k=k_, block_n=n,
                          backward=backward)
                     for form, k_, n, backward in (
                         (0, d, 2 * width, 0), (0, width, d, 0),
                         (0, d, 2 * width, 1), (1, d, width, 1),
                         (1, 2 * width, d, 1), (2, d, 2 * width, 1),
                         (2, width, d, 1))]
    assert np.array_equal(got[1], want[1])
    for a, b in zip(got, want):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


def test_on_a_kind_of_tpu_nobody_read_ragged_dot_stays(monkeypatch):
    """A TPU whose kind ``mosaic.vmem_bytes`` does not know (the kernels'
    blocks ask for more VMEM than the compiler's own limit): the shapes a
    v5e finds tiles for find none, all seven grouped matmuls are
    ``ragged_dot``'s and their records say ``kernel`` 0."""
    from ray_tpu.ops import grouped_matmul
    T, k, d, width, held = 128, 4, 192, 128, 4
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    x = jax.random.normal(keys[0], (T, d))
    experts = jax.random.randint(keys[1], (T, k), 0, held)
    weights = jnp.full((T, k), 0.25)
    wi = jax.random.normal(keys[2], (held, d, 2 * width)) * 0.1
    wo = jax.random.normal(keys[3], (held, width, d)) * 0.1
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(mosaic, "device_kind", lambda: "TPU v4")
    monkeypatch.setattr(moe, "_unwritten", jnp.zeros)
    for name in ("by_group", "per_group"):
        monkeypatch.setattr(grouped_matmul, name,
                            lambda *a, **kw: pytest.fail("a kernel"))
    steptrace.set_enabled(True)
    steptrace.reset()
    try:
        jax.clear_caches()             # the layer's passes are jitted
        jax.grad(lambda x, wi, wo: moe.held_expert_ffn(
            x, experts, weights, wi, wo, index=0, of=1)[0].sum(),
            argnums=(0, 1, 2))(x, wi, wo)
        drawn = [e["args"] for e in steptrace.chrome_trace(
            steptrace.merge_records(steptrace.snapshot()))
            if e["ph"] == "C" and e["name"] == "moe/grouped_matmul"]
    finally:
        steptrace.set_enabled(False)
    monkeypatch.undo()
    jax.clear_caches()
    assert [(r["kernel"], r["form"], r["tile"]) for r in drawn] == [
        (0, form, 0) for form in (0, 0, 0, 1, 1, 2, 2)]


def test_a_traced_pass_counts_its_row_buffers_once():
    """Two layers of one shape, forward and backward: each pass is traced
    once and writes one ``moe/row_buffers`` record, which a timeline draws;
    off a TPU every buffer starts from zeros (``unwritten`` 0)."""
    layer = _layer()
    experts, weights = moe.topk_routing(layer["x"], layer["router"],
                                        layer["bias"], layer["k"])

    def loss(x):
        for _ in range(2):
            x, _ = moe.held_expert_ffn(x, experts, weights, layer["wi"][:4],
                                       layer["wo"][:4], index=0, of=2)
        return x.sum()

    steptrace.set_enabled(True)
    steptrace.reset()
    try:
        jax.clear_caches()  # the record is written where a pass is traced
        jax.grad(loss)(layer["x"])
        drawn = [e["args"] for e in steptrace.chrome_trace(
            steptrace.merge_records(steptrace.snapshot()))
            if e["ph"] == "C" and e["name"] == "moe/row_buffers"]
    finally:
        steptrace.set_enabled(False)
    rows = len(moe.row_buffer_rungs(_PAIRS)) * moe.row_buffer_rungs(_PAIRS)[0]
    d, width = 32, 16
    assert drawn == [
        {"buffers": 2, "rows": rows, "bytes": 4 * rows * (d + width),
         "unwritten": 0, "backward": 0},
        {"buffers": 5, "rows": rows, "unwritten": 0, "backward": 1,
         "bytes": 4 * rows * (2 * d + 3 * width) + 4 * rows}]


def test_routing_is_the_published_rule_on_a_hand_made_case():
    """Two tokens, four experts, two a token. The bias moves the selection
    (token 0 takes expert 3 for expert 1) and not the weights, which are the
    chosen experts' own scores over their sum, times the scale."""
    logits = jnp.log(jnp.array([[0.8, 0.6, 0.3, 0.5],
                                [0.2, 0.7, 0.9, 0.1]]) /
                     (1 - jnp.array([[0.8, 0.6, 0.3, 0.5],
                                     [0.2, 0.7, 0.9, 0.1]])))
    x, router = jnp.eye(2), logits                     # x @ router = logits
    experts, weights = moe.topk_routing(x, router, jnp.zeros(4), 2, 2.5)
    assert np.asarray(experts).tolist() == [[0, 1], [2, 1]]
    np.testing.assert_allclose(
        weights, [[2.5 * 0.8 / 1.4, 2.5 * 0.6 / 1.4],
                  [2.5 * 0.9 / 1.6, 2.5 * 0.7 / 1.6]], rtol=1e-5)
    bias = jnp.array([0.0, 0.0, 0.0, 0.2])             # 0.5 + 0.2 > 0.6
    experts, weights = moe.topk_routing(x, router, bias, 2, 2.5)
    assert np.asarray(experts).tolist() == [[0, 3], [2, 1]]
    np.testing.assert_allclose(
        weights, [[2.5 * 0.8 / 1.3, 2.5 * 0.5 / 1.3],
                  [2.5 * 0.9 / 1.6, 2.5 * 0.7 / 1.6]], rtol=1e-5)
    # the bias takes no gradient, the router does
    g_bias, g_router = jax.grad(
        lambda b, r: moe.topk_routing(x, r, b, 2, 2.5)[1][0, 0],
        argnums=(0, 1))(bias, router)
    assert not np.asarray(g_bias).any() and np.asarray(g_router).any()


def test_the_expert_layers_gradients_are_the_dense_computations():
    layer, of, index = _layer(seed=4), 2, 0
    shared = _shared()

    def system(x, router, wi, wo):
        y, _ = _held_part(dict(layer, x=x, router=router, wi=wi, wo=wo),
                          index, of)
        return (y ** 2).sum()

    def plain(x, router, wi, wo):
        part = dict(layer, x=x, router=router, wi=wi, wo=wo)
        with jax.default_matmul_precision("highest"):
            y = (_reference_layer(part, index, of, shared)
                 - REFERENCE._swiglu(x, shared))
        return (y ** 2).sum()

    args = (layer["x"], layer["router"], layer["wi"], layer["wo"])
    for got, want in zip(jax.grad(system, argnums=(0, 1, 2, 3))(*args),
                         jax.grad(plain, argnums=(0, 1, 2, 3))(*args)):
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-4)


# ----------------------------------------------------------------------
# attention with keys wider than values
# ----------------------------------------------------------------------

@pytest.mark.parametrize("impl", ["pallas_interpret", "scan"])
@pytest.mark.parametrize("widths", [(48, 32), (24, 16), (32, 32)],
                         ids=["48x32", "24x16", "equal"])
def test_flash_paths_at_a_key_width_and_a_value_width(impl, widths):
    d_qk, d_v = widths
    keys = jax.random.split(jax.random.PRNGKey(d_qk), 4)
    q = jax.random.normal(keys[0], (2, 256, d_qk))
    k = jax.random.normal(keys[1], (2, 256, d_qk))
    v = jax.random.normal(keys[2], (2, 256, d_v))
    g = jax.random.normal(keys[3], (2, 256, d_v))

    def through(fn):
        return jax.value_and_grad(
            lambda q, k, v: (fn(q, k, v) * g).sum(), argnums=(0, 1, 2))(q, k, v)

    got = through(lambda q, k, v: attention.flash_attention(
        q, k, v, causal=True, impl=impl, block_q=128, block_k=128))
    want = through(lambda q, k, v: attention.attention_reference(
        q, k, v, causal=True))
    assert got[0].shape == () and got[1][2].shape == v.shape
    np.testing.assert_allclose(got[0], want[0], rtol=1e-4)
    for a, b in zip(got[1], want[1]):
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=2e-5)


def test_auto_attention_decides_by_both_widths(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    shape = lambda d: jax.ShapeDtypeStruct((2, 8192, 32, d), jnp.bfloat16)
    assert attention.auto_attention(shape(192), shape(128)) == "flash"
    assert attention.auto_attention(shape(128), shape(128)) == "flash"
    assert attention.auto_attention(shape(64)) == "flash"
    # a pair the kernel was not measured at stays with XLA's program
    assert attention.auto_attention(shape(192), shape(64)) == "xla"
    assert attention.auto_attention(shape(192)) == "xla"


def test_the_xla_path_takes_values_of_another_width():
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(keys[0], (2, 64, 4, 24))
    k = jax.random.normal(keys[1], (2, 64, 4, 24))
    v = jax.random.normal(keys[2], (2, 64, 4, 16))
    bhsd = lambda t: t.transpose(0, 2, 1, 3)
    want = attention.attention_reference(bhsd(q), bhsd(k), bhsd(v),
                                         causal=True).transpose(0, 2, 1, 3)
    for path in ("xla", "flash", "auto"):
        np.testing.assert_allclose(
            attention.causal_self_attention(q, k, v, path), want,
            rtol=1e-4, atol=1e-5)


def test_equal_widths_ask_the_compiler_for_nothing_new():
    """Up to 128 lanes the kernels' compiler parameters are what they were:
    no VMEM limit of their own."""
    assert flash_kernels._compiler_params(False, 64).vmem_limit_bytes is None
    assert flash_kernels._compiler_params(False, 128).vmem_limit_bytes is None
    assert flash_kernels._compiler_params(False, 192).vmem_limit_bytes == 2**25


# ----------------------------------------------------------------------
# the loss walk in its new home
# ----------------------------------------------------------------------

@pytest.mark.parametrize("masked", [False, True])
def test_the_walk_over_an_untied_head_is_the_fused_loss(masked):
    keys = jax.random.split(jax.random.PRNGKey(1), 4)
    h = jax.random.normal(keys[0], (2, 32, 16))
    head = jax.random.normal(keys[1], (50, 16)) * 0.3
    labels = jax.random.randint(keys[2], (2, 32), 0, 50)
    mask = (jax.random.uniform(keys[3], (2, 32)) > 0.3) if masked else None

    def fused(h, head):
        return xent.fused_xent(h @ head.T, labels, mask)

    def walked(h, head):
        return xent.chunked_xent(h, head, labels, mask, n_chunks=4)

    got = jax.value_and_grad(walked, argnums=(0, 1))(h, head)
    want = jax.value_and_grad(fused, argnums=(0, 1))(h, head)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    for a, b in zip(got[1], want[1]):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


def test_gpt2s_loss_is_the_moved_walk_bit_for_bit():
    """``gpt2.loss_fn`` calls ``ops.xent.chunked_xent`` with the tied
    embedding: the names it kept are the moved functions, and its loss and
    gradient are those of the direct call, to the bit."""
    assert gpt2.chunked_xent is xent.chunked_xent
    assert gpt2.fused_xent is xent.fused_xent
    config = gpt2.GPT2Config.small_test(loss_chunks=4, dtype=jnp.float32)
    model, params = gpt2.init_params(config, jax.random.PRNGKey(0))
    batch = gpt2.synthetic_batch(jax.random.PRNGKey(1), 2, 32,
                                 config.vocab_size)

    def direct(params):
        hidden = model.apply({"params": params}, batch["input_ids"],
                             return_hidden=True)
        return xent.chunked_xent(hidden, params["wte"]["embedding"],
                                 batch["labels"], None, n_chunks=4)

    got = jax.value_and_grad(lambda p: gpt2.loss_fn(p, model, batch))(params)
    want = jax.value_and_grad(direct)(params)
    assert float(got[0]) == float(want[0])
    for a, b in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# ----------------------------------------------------------------------
# the model
# ----------------------------------------------------------------------

def test_the_second_terms_targets_are_the_labels_shifted_once_more():
    labels = jnp.arange(12).reshape(2, 6) + 100
    targets, keep = mla_moe.second_term_targets(labels)
    assert np.asarray(targets[:, :-1]).tolist() == np.asarray(
        labels[:, 1:]).tolist()
    assert np.asarray(keep).tolist() == [[1, 1, 1, 1, 1, 0]] * 2
    mask = jnp.array([[1, 1, 0, 1, 1, 1], [1, 1, 1, 1, 1, 0]])
    _, keep = mla_moe.second_term_targets(labels, mask)
    assert np.asarray(keep).tolist() == [[1, 0, 1, 1, 1, 0],
                                         [1, 1, 1, 1, 0, 0]]


def _small(**kw):
    config = mla_moe.MLAMoEConfig.small_test(dtype=jnp.float32,
                                             expert_shard=(1, 2), **kw)
    model, params, tx, opt_state = mla_moe.make_train_state(
        config, jax.random.PRNGKey(0))
    tokens = _tokens(7, config.vocab_size, 2, 32)
    batch = {"input_ids": jnp.asarray(tokens[:, :-1]),
             "labels": jnp.asarray(tokens[:, 1:])}
    return config, model, params, tx, opt_state, batch


def test_the_loss_is_its_two_terms_and_the_last_position_does_not_count():
    config, model, params, _, _, batch = _small()
    loss, aux = mla_moe.loss_fn(params, model, batch)
    np.testing.assert_allclose(
        loss, aux["main"] + config.mtp_loss_weight * aux["mtp"], rtol=1e-6)
    assert aux["tokens_per_expert"].shape == (3, 4)     # 2 layers + module
    # every token's pairs on held experts, in each expert layer
    assert (np.asarray(aux["tokens_per_expert"]).sum(axis=1) <= 64 * 3).all()
    # the second term ignores what follows the last label: change the last
    # label and only the terms that read it move
    other = dict(batch, labels=batch["labels"].at[:, -1].add(1) % 256)
    _, moved = mla_moe.loss_fn(params, model, other)
    assert float(moved["main"]) != float(aux["main"])
    # without the module the loss is its first term
    plain, _, params0, _, _, _ = _small(num_nextn_predict_layers=0)
    model0 = mla_moe.MLAMoE(plain)
    loss0, aux0 = mla_moe.loss_fn(params0, model0, batch)
    assert float(aux0["mtp"]) == 0.0 and float(loss0) == float(aux0["main"])
    assert "mtp_block" not in params0


def test_recomputation_and_the_whole_logits_change_no_value():
    config, model, params, _, _, batch = _small()
    base = jax.value_and_grad(
        lambda p: mla_moe.loss_fn(p, model, batch)[0])(params)
    for change in ({"remat": True}, {"loss_chunks": 0}):
        other = mla_moe.MLAMoE(dataclasses.replace(config, **change))
        got = jax.value_and_grad(
            lambda p: mla_moe.loss_fn(p, other, batch)[0])(params)
        np.testing.assert_allclose(got[0], base[0], rtol=1e-6)
        for a, b in zip(jax.tree.leaves(got[1]), jax.tree.leaves(base[1])):
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-7)


def _gradient(config, batch):
    return jax.grad(
        lambda p: mla_moe.loss_fn(p, mla_moe.MLAMoE(config), batch)[0])


@pytest.mark.parametrize("policy,forward", [(True, 1), (False, 2)],
                         ids=["kept", "no_policy"])
def test_a_recomputed_block_keeps_what_only_the_kernel_makes(
        monkeypatch, policy, forward):
    """Under ``remat`` a block's backward pass reruns its projections and
    not the forward kernel: ``ops.remat.remat_policy`` keeps the
    kernel's output and log-sum-exp by name. The gradient's jaxpr holds
    the forward kernel once a block (three layers and the prediction
    module) and twice without the policy."""
    config, _, params, _, _, batch = _small(remat=True, attention="flash")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if not policy:
        monkeypatch.setattr(mla_moe, "remat_policy", lambda: None)
    jax.clear_caches()  # flash_attention is jitted: drop earlier decisions
    try:
        calls = kernel_calls(jax.make_jaxpr(_gradient(config, batch))(params))
    finally:
        jax.clear_caches()
    blocks = config.num_hidden_layers + config.num_nextn_predict_layers
    # each expert layer's loops start from buffers nobody filled (PR 46):
    # two forward and five backward (a recomputed forward's layer is dead:
    # the block ends with it, and the backward rule makes its own rows)
    expert = blocks - config.first_k_dense_replace
    assert calls.pop("unwritten") == (2 + 5) * expert
    assert calls == {"flash_fwd": forward * blocks, "flash_bwd": blocks}


def test_without_the_kernel_the_policy_keeps_nothing(monkeypatch):
    """``attention="xla"`` makes no such name: the program is the one that
    recomputes under no policy."""
    config, _, params, _, _, batch = _small(remat=True, attention="xla")
    lowered = lambda: jax.jit(_gradient(config, batch)).lower(params).as_text()
    kept = lowered()
    monkeypatch.setattr(mla_moe, "remat_policy", lambda: None)
    assert kept == lowered()


def test_the_kept_output_is_the_one_the_kernel_would_write_again(monkeypatch):
    """The kernel path (interpreted here): gradients with the blocks
    recomputed, their kernel outputs kept, are those without recomputation
    bit for bit."""
    monkeypatch.setattr(attention, "flash_attention", functools.partial(
        attention.flash_attention, impl="pallas_interpret"))
    config, _, params, _, _, batch = _small(attention="flash")
    plain = jax.jit(_gradient(config, batch))(params)
    kept = jax.jit(_gradient(
        dataclasses.replace(config, remat=True), batch))(params)
    for a, b in zip(jax.tree.leaves(kept), jax.tree.leaves(plain)):
        np.testing.assert_array_equal(a, b)


def test_the_step_is_the_one_builder_and_reports_its_parts():
    """``mla_moe.build_train_step`` is ``parallel.build_train_step`` with the
    auxiliary output; the selection bias stays where it was initialised; a
    loop's report carries the two terms and the experts' load, and the step
    observatory gets them as one record."""
    config, model, params, tx, opt_state, batch = _small()
    pairs = batch["input_ids"].size * config.num_experts_per_tok
    step = mla_moe.build_train_step(model, tx, donate=False)
    new_params, _, loss, main, mtp, tokens = step(params, opt_state, batch)
    assert not np.asarray(
        new_params["layers_1"]["moe"]["router_bias"]).any()
    assert np.asarray(new_params["layers_1"]["moe"]["router"] !=
                      params["layers_1"]["moe"]["router"]).any()
    steptrace.set_enabled(True)
    steptrace.reset()
    try:
        metrics = mla_moe.step_metrics(loss, main, mtp, tokens,
                                       pairs=pairs)
        records = [r for r in steptrace.snapshot()
                   if r["kind"] == "counters"]
    finally:
        steptrace.set_enabled(False)
    assert set(metrics) == {"loss", "loss_main", "loss_mtp",
                            "expert_tokens_max", "expert_tokens_mean",
                            "rows_present", "rows_buffered", "rows_fill"}
    present = np.asarray(tokens).sum(axis=1)            # a layer's pairs
    assert metrics["rows_present"] == int(present.sum())
    # a loop that does not say how many pairs a step has: the count alone
    assert set(mla_moe.step_metrics(loss, main, mtp, tokens)) == set(
        metrics) - {"rows_buffered", "rows_fill"}
    rungs = moe.row_buffer_rungs(pairs)
    assert metrics["rows_buffered"] == sum(
        min(n for n in rungs if n >= p) for p in present)
    assert metrics["rows_fill"] == pytest.approx(
        metrics["rows_present"] / metrics["rows_buffered"])
    assert 0 < metrics["rows_fill"] <= 1
    assert metrics["expert_tokens_max"] == int(np.asarray(tokens).max())
    assert metrics["expert_tokens_mean"] == pytest.approx(
        float(np.asarray(tokens).mean()))
    assert len(records) == 1 and records[0]["name"] == "train/step_aux"
    assert records[0]["values"] == metrics
    merged = steptrace.merge_records(records)
    assert merged["counters"][0]["values"]["loss_mtp"] == metrics["loss_mtp"]
    # and a timeline draws them: a counter event named as the record
    drawn = [e for e in steptrace.chrome_trace(merged) if e["ph"] == "C"]
    assert [(e["name"], e["args"]) for e in drawn] == [
        ("train/step_aux", metrics)]


def test_the_familys_count_is_the_state_the_program_makes():
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "joyai-llm-flash.json")) as f:
        model = json.load(f)
    built = FAMILY.build(model, {"remat": True}, None)
    params, _ = jax.eval_shape(built.make_state, jax.random.PRNGKey(0))
    made = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    assert FAMILY.num_params(model) == made == 680_441_088
    # 6 x the matmul parameters a token uses + attention at 8k: 4.91 GFLOP
    assert FAMILY.train_flops_per_token(model, 8192) == pytest.approx(
        4.908e9, rel=1e-3)
    # half an expert a layer is the routed part's expectation here
    assert FAMILY.matmul_params_per_token(model) == pytest.approx(
        314.7e6, rel=1e-3)
