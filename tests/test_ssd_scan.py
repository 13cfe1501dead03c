"""The scalar-decay state-space scan (``ray_tpu/ops/ssm.py:ssd_scan``,
Mamba-2's recurrence): the kernels ``ssd_fwd`` / ``ssd_bwd`` in interpret
mode and the chunked twin against the plain recurrence one position a step,
for ``y`` and the gradients of ``x``, ``dt``, ``A``, ``B``, ``C`` and ``D``:
several chunks a stride, several strides a block, several blocks a sequence,
two sequences, 8 and 4 heads a group, decays near 0 and near 1, a length off
the stride (padded); what the custom_vjp names for the recomputation policy,
what the kernels are named and write into the runtime's ring, and where
``impl=None`` takes them."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops import ssd_scan, ssm
from ray_tpu.ops.remat import remat_policy
from tests.conftest import kernel_calls

F32 = jnp.float32
NAMES = ("y", "dx", "ddt", "dA", "dB", "dC", "dD")


def plain(x, dt, a, b, c, skip):
    """One position a step, each sequence from a zero state, float32."""
    batch, _, heads, p = x.shape
    groups, states = b.shape[2:]
    x, dt, b, c = (t.astype(F32) for t in (x, dt, b, c))
    b, c = (jnp.repeat(t, heads // groups, axis=2) for t in (b, c))

    def step(state, inputs):
        x_t, dt_t, b_t, c_t = inputs
        state = (jnp.exp(dt_t * a)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return state, (jnp.einsum("bhpn,bhn->bhp", state, c_t)
                       + skip[:, None] * x_t)

    swap = lambda t: jnp.swapaxes(t, 0, 1)
    _, y = lax.scan(step, jnp.zeros((batch, heads, p, states), F32),
                    tuple(map(swap, (x, dt, b, c))))
    return swap(y)


def _operands(batch, length, heads, groups, dtype=F32, dt_scale=1.0, seed=0,
              head_dim=64, states=128):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(ks[0], (batch, length, heads, head_dim)).astype(
        dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (batch, length, heads))
                         - 2.0) * dt_scale
    a = -jnp.exp(jax.random.uniform(ks[2], (heads,), minval=-1.0, maxval=2.5))
    b, c = (0.3 * jax.random.normal(k, (batch, length, groups, states)).astype(
        dtype) for k in ks[3:5])
    return (x, dt, a, b, c, jax.random.normal(ks[5], (heads,)),
            jax.random.normal(ks[6], x.shape))


def _out_and_grads(fn, *operands):
    *ops, w = operands
    y, pull = jax.vjp(lambda *o: fn(*o).astype(F32), *ops)
    return (y, *pull(w))


def _by(impl, chunk=None):
    return lambda *o: ssd_scan(*o, impl=impl, chunk=chunk)


def _close(got, want, tol):
    for name, a, b in zip(NAMES, got, want):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        assert np.abs(a - b).max() <= tol * np.abs(b).max(), (
            name, np.abs(a - b).max() / np.abs(b).max())


@pytest.fixture
def short_blocks(monkeypatch):
    """Grid blocks of 256 positions: a sequence of 512 has two, the state
    and its gradient carried between them in the kernels' scratch."""
    monkeypatch.setattr(ssm, "_SSD_BLOCK", 256)
    jax.clear_caches()
    yield
    jax.clear_caches()


# (batch, length, heads, groups, scale of dt): 8 heads a group over four
# strides of one chunk in two blocks; 4 heads a group, two groups; decays
# near 0 (dt A down to -60 a position: a state forgotten within a chunk)
# and near 1 (dt A about -1e-4: a state that crosses every boundary); a
# length off the stride (200 -> 256, padded with dt 0)
_CASES = [(1, 512, 8, 1, 1.0), (1, 256, 8, 2, 1.0), (1, 256, 4, 1, 40.0),
          (1, 384, 4, 1, 1e-3), (2, 200, 4, 1, 1.0)]
_IDS = lambda c: "x".join(map(str, c))


@functools.lru_cache(maxsize=None)
def _case(case):
    """(operands, the recurrence's answers): made once for both forms."""
    batch, length, heads, groups, dt_scale = case
    xs = _operands(batch, length, heads, groups, dt_scale=dt_scale,
                   seed=length)
    return xs, _out_and_grads(jax.jit(plain), *xs)


@pytest.mark.parametrize("impl", ["scan", "pallas_interpret"])
@pytest.mark.parametrize("case", _CASES, ids=_IDS)
def test_scan_matches_the_recurrence(case, impl, short_blocks):
    xs, want = _case(case)
    # under the heavy decay float32 sums of terms that nearly cancel differ
    # by their order: a looser limit there
    _close(_out_and_grads(_by(impl), *xs), want,
           2e-4 if case[-1] > 1 else 3e-5)


@pytest.mark.parametrize("impl", ["scan", "pallas_interpret"])
def test_bfloat16_operands_keep_a_float32_state(impl):
    """bfloat16 x, B, C (stride 256: two chunks a stride, the backward makes
    the second chunk's starting state again): ``y`` in bfloat16, within the
    operands' rounding of the recurrence on the same operands; the twin and
    the kernels agree to the same."""
    xs = _operands(1, 512, 4, 2, jnp.bfloat16, seed=3)
    got = _out_and_grads(_by(impl), *xs)
    assert got[0].dtype == F32 and ssd_scan(*xs[:6], impl=impl).dtype \
        == jnp.bfloat16
    assert got[1].dtype == got[4].dtype == jnp.bfloat16
    assert got[2].dtype == got[3].dtype == got[6].dtype == F32
    _close(got, _out_and_grads(plain, *xs), 2e-2)


def test_sequences_are_independent():
    """Two sequences a batch against each alone: no state, and no gradient
    of one, crosses into the other (the kernels' scratch is zeroed at each
    sequence's first and last block)."""
    xs = _operands(2, 128, 4, 1, seed=5)
    for impl in ("scan", "pallas_interpret"):
        together = _out_and_grads(_by(impl), *xs)
        for n in range(2):
            alone = _out_and_grads(_by(impl), *(
                t[n:n + 1] if t.ndim > 1 else t for t in xs))
            for i in (0, 1, 2, 4, 5):
                np.testing.assert_allclose(together[i][n], alone[i][0],
                                           rtol=1e-5, atol=1e-6)


def test_boundaries_weigh_no_more_than_the_output():
    assert ssm.ssd_stride_of(128, 128, 2) == 256
    assert ssm.ssd_stride_of(128, 128, 4) == 128
    assert ssm.ssd_stride_of(64, 128, 2) == 256
    assert ssm.ssd_stride_of(16, 16, 4) == 16
    # a head's [64, 128] float32 state against 256 positions of 64 bfloat16
    assert 64 * 128 * 4 == 256 * 64 * 2


def test_kernels_are_named_and_recorded():
    """The two ``pallas_call``s carry the names the benchmark's readers look
    for (which the selective scan's readers do not match), they take the
    model's own arrays, and each traced pass writes one ``ssd/scan``
    record."""
    from perfbench.metrics.ssm_scan_ms import KERNEL
    from ray_tpu._private import steptrace

    shapes = [jax.ShapeDtypeStruct(s, d) for s, d in (
        ((2, 8192, 64, 64), jnp.bfloat16), ((2, 8192, 64), F32),
        ((64,), F32), ((2, 8192, 8, 128), jnp.bfloat16),
        ((2, 8192, 8, 128), jnp.bfloat16), ((64,), F32))]
    grad = jax.grad(lambda *o: ssd_scan(*o, impl="pallas").astype(F32).sum(),
                    argnums=tuple(range(6)))
    steptrace.set_enabled(True)
    steptrace.reset()
    try:
        jax.clear_caches()
        jaxpr = jax.make_jaxpr(grad)(*shapes)
        records = [r["values"] for r in steptrace.snapshot()
                   if r["kind"] == "counters" and r["name"] == "ssd/scan"]
    finally:
        steptrace.set_enabled(False)
        jax.clear_caches()
    assert kernel_calls(jaxpr) == {"ssd_fwd": 1, "ssd_bwd": 1}
    for name in kernel_calls(jaxpr):
        assert not name.startswith("ssm_scan_") and not KERNEL.match(
            f'%{name}.1 = bf16[2] custom-call(), '
            'custom_call_target="tpu_custom_call"')
    text = str(jaxpr)
    assert "bf16[2,8192,4096]" in text and "bf16[2,8192,1024]" in text
    assert "f32[2,32,32,128,128]" in text     # the boundaries, 256 apart
    tokens = 2 * 8192
    assert {r["backward"] for r in records} == {0, 1}
    for r in records:
        assert r == {"heads": 64, "groups": 8, "head_dim": 64, "states": 128,
                     "tokens": tokens, "sequences": 2, "chunk": 128,
                     "stride": 256, "boundary_bytes": tokens * 4096 * 2,
                     "bytes_needed": tokens * (33_280 if r["backward"]
                                               else 20_736),
                     "backward": r["backward"]}


def test_recomputation_keeps_the_scan():
    """Under ``ops.remat.remat_policy`` a recomputed function's
    backward pass does not run the forward kernel again: the output and the
    boundary states are named and kept."""
    xs = _operands(1, 256, 4, 1, seed=7)[:6]

    def layer(*o):
        return jnp.tanh(ssd_scan(o[0] * 2.0, *o[1:],
                                 impl="pallas_interpret")).sum()

    def calls(policy):
        fn = jax.checkpoint(layer, policy=policy)
        return kernel_calls(jax.make_jaxpr(jax.grad(fn))(*xs))

    assert calls(remat_policy()) == {"ssd_fwd": 1, "ssd_bwd": 1}
    assert calls(None) == {"ssd_fwd": 2, "ssd_bwd": 1}


def test_auto_takes_the_kernels_on_a_tpu_where_the_layout_fits(monkeypatch):
    x, b = jnp.zeros((2, 256, 8, 64)), jnp.zeros((2, 256, 2, 128))
    assert ssm.ssd_auto_impl(x, b) == "scan"            # a CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ssm.ssd_auto_impl(x, b) == "pallas"
    # a head 128 or 16 wide, an odd number of heads a group, states off the
    # lanes: the twin's
    assert ssm.ssd_auto_impl(jnp.zeros((2, 256, 8, 128)), b) == "scan"
    assert ssm.ssd_auto_impl(jnp.zeros((2, 256, 8, 16)), b) == "scan"
    assert ssm.ssd_auto_impl(jnp.zeros((2, 256, 6, 64)), b) == "scan"
    assert ssm.ssd_auto_impl(x, jnp.zeros((2, 256, 2, 16))) == "scan"


def test_small_heads_and_states_run_the_twin():
    """A toy model's sizes (heads of 16, 16 states, chunks of 16) under
    ``impl=None``: the twin's values, no kernel."""
    xs = _operands(2, 64, 4, 2, seed=9, head_dim=16, states=16)
    fn = lambda *o: ssd_scan(*o, chunk=16)
    assert not kernel_calls(jax.make_jaxpr(fn)(*xs[:6]))
    _close(_out_and_grads(fn, *xs), _out_and_grads(plain, *xs), 3e-5)
