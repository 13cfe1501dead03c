"""The main path's kernels and train steps, compiled for a described v5e.

No chip is attached: the TPU compiler installed here compiles for a
topology that is only described (on-chip-measurement guide, section 2).
What it refuses — a Mosaic kernel it cannot lower, a sharded program it
cannot partition, a step that does not fit HBM — it would refuse on the
chip too, so these run before every chip call at no chip time. Nothing
executes: a pass here says nothing about results or speed.

All tests live in this one file and describe the topology inside a
module-scoped fixture: only one process may load the TPU library, and it
must be the xdist worker that was handed this file.
"""

import collections
import functools
import hashlib
import json
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from ray_tpu.models import gpt2
from ray_tpu.ops import moe
from ray_tpu.ops.attention import flash_attention
from ray_tpu.parallel import state_shardings

HBM_BYTES = 16 * 1024**3  # one v5e chip
BATCH, SEQ = 16, 1024


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without a chip: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


@pytest.fixture
def on_tpu(monkeypatch):
    """Steer code that asks ``jax.default_backend()`` (the flash
    dispatcher, the model's ``attention="auto"``) onto its TPU branch, and
    code that asks the device's kind (``mosaic.vmem_bytes``) onto the
    described chip's: the process itself sees the CPU."""
    from ray_tpu.ops import mosaic

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(mosaic, "device_kind", lambda: "TPU v5 lite")
    jax.clear_caches()
    yield
    jax.clear_caches()


def _with_sharding(tree, sharding):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _train_step_args(attention, state_sharding, batch_sharding, batch=BATCH):
    """(jitted step, abstract args) for the full-width GPT-2-124M step."""
    config = gpt2.GPT2Config.gpt2_124m(loss_chunks=8, attention=attention)
    model = gpt2.GPT2(config)
    tx = gpt2.make_optimizer()

    def state(rng):
        _, params, _, opt_state = gpt2.make_train_state(config, rng)
        return params, opt_state

    params, opt_state = _with_sharding(
        jax.eval_shape(state, jax.random.PRNGKey(0)), state_sharding)
    ids = jax.ShapeDtypeStruct((batch, SEQ), jnp.int32,
                               sharding=batch_sharding)
    step = gpt2.build_train_step(model, tx, donate=True)
    return step, (params, opt_state, {"input_ids": ids, "labels": ids})


# sha256 (first 16 digits) of the jaxpr that each accepted cell's step lowers
# from: the program as jax hands it to the lowering, every kernel's body in
# it, with no file and no line (the Mosaic payloads in the lowered text carry
# the line of every frame, so that text moves with any edit above a kernel).
# They are the parent's of PR 55 (commit 9501e07) but where a later PR is
# named, read there under this file: a PR that means to leave a cell's step
# alone finds here, before any chip call, whether it did; one that means to
# move it re-anchors the cell's digest (it is printed) and says so.
_HELD_PROGRAMS = {
    "gpt2-124m.step": "d12d86d6c3b58f16",
    "gpt2-xl.step-fsdp4": "dd746428dd36d2db",
    "joyai-llm-flash.step-8k": "3df743f71e94ff9d",   # PR 61
    "phi-4-mini-flash.step-one-seq": "d1a800cb91c9c326",
    "lfm2-8b-a1b.step-8k": "73a6b74898269edd",   # PR 61
    "qwen3-next-80b-a3b.step-8k": "9d394435fa05898f",   # PR 61
    "nemotron-3-nano-30b-a3b.step-8k": "a33f055570590fef",   # PR 64
    "trinity-mini.step-16k": "69c63c6279bc5a07",   # PR 66
    "mellum2-12b-a2.5b.step-8k": "82ab99b9a5fbbc45",   # PR 66
    "sdar-30b-a3b-chat.step-bd-4k": "e6cb997ca8d2efbf",   # PR 65
    "keye-vl-2.0-30b-a3b.step-16k-img": "1c2c2a4256a8fa75",   # PR 68
}


def _lower_held(cell, step, *args):
    """``step.lower(*args)``, with the jaxpr it lowers from held to
    ``_HELD_PROGRAMS[cell]`` (addresses of function objects, which a
    ``custom_vjp`` or a policy prints, struck out)."""
    traced = step.trace(*args)
    text = re.sub(r" at 0x[0-9a-f]+", "", str(traced.jaxpr))
    digest = hashlib.sha256(text.encode()).hexdigest()[:16]
    print(f"program {cell}: {digest}")
    assert digest == _HELD_PROGRAMS[cell], (cell, digest)
    return traced.lower()


def _device_bytes(compiled):
    m = compiled.memory_analysis()
    return (m.temp_size_in_bytes + m.argument_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes)


@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "fwd_bwd"])
@pytest.mark.parametrize(
    "batch,heads,seq,widths",
    [(None, 16 * 12, SEQ, (64, 64)), (None, 4 * 12, 4096, (64, 64)),
     (None, 2 * 32, 8192, (192, 128)),
     (16, 12, SEQ, (64, 64)), (16, 25, SEQ, (64, 64)),
     (8, 32, 2048, (128, 128))],
    ids=["gpt2_1024", "several_grid_blocks_4096", "latent_8192_192_128",
         "model_arrays_12_heads_1024_64", "model_arrays_25_heads_1024_64",
         "model_arrays_32_heads_2048_128"])
def test_flash_kernel_compiles(topo, no_compile_cache, batch, heads, seq,
                               widths, backward):
    """(batch 16 x 12 heads, 1024, 64) bf16 — one grid step a head, the tile
    walk unrolled; 4096, past ``_MAX_RESIDENT``: 2 x 2 grid blocks a head,
    each walked by its kind (one whole, two on the diagonal, one dead: a
    branch on the grid position, inside it straight-line code as at 1024),
    dead blocks clamped in the index maps; the latent-attention cell's
    call, 2 x 32 heads of 8192 with keys 192 and values 128 wide: 4 x 4
    grid blocks a head. With a ``batch``, the kernels that address a
    model's own [B, T, H x d] (PR 51): the shape GPT-2's step calls, two
    64-wide heads a lane tile and the turns made in VMEM; GPT-2 XL's 25
    heads, whose thirteenth tile lies half past the arrays' edge; and one
    128-wide head a tile at the longest one-block length."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    shape = lambda width: ((heads, seq, width) if batch is None
                           else (batch, seq, heads * width))
    q = jax.ShapeDtypeStruct(shape(widths[0]), jnp.bfloat16,
                             sharding=one_chip)
    v = jax.ShapeDtypeStruct(shape(widths[1]), jnp.bfloat16,
                             sharding=one_chip)

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, impl="pallas",
                               heads=None if batch is None else heads)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2)) if backward else fwd
    text = jax.jit(fn).lower(q, q, v).compile().as_text()
    assert "tpu_custom_call" in text
    if batch is not None and heads * widths[0] % 128 == 0:
        # nothing is turned outside the kernels (1,600 lanes are no whole
        # number of tiles: the compiler lays such an argument out with the
        # tokens minor, and copies it to the row-major the kernel reads)
        assert not re.findall(r" (?:copy|transpose)\(", text)


@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "fwd_bwd"])
@pytest.mark.parametrize("window", [2048, None], ids=["w2048", "full"])
def test_flash_kernel_with_results_in_the_models_arrays_compiles(
        topo, no_compile_cache, window, backward):
    """The window cell's calls (PR 55): one sequence of 16,384 tokens, 32
    query heads on 4 key-value heads of 128, eight blocks of keys a head,
    under the 2,048-key window and without. q, k and v go in as a model's
    [1, T, heads x 128] and XLA folds them; O leaves the forward kernel as
    the model's ``bf16[1,16384,4096]``, dK and dV the backward kernel as
    ``bf16[1,16384,512]``, with no copy of any of them and none of the
    cotangent, and ``delta`` is the kernel's (no reduction outside it); of
    dQ the float32 [32, 128, 16384] sum is turned by XLA as before. O's
    accumulator is turned in VMEM and so is the dO x O product (what Mosaic
    makes of a (128, 512) and a (256, 128) float32 turn, and of the 12 MiB
    the backward holds, is this test's to find)."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    q, kv, w = (jax.ShapeDtypeStruct((1, 16384, n * 128), jnp.bfloat16,
                                     sharding=one_chip) for n in (32, 4, 32))

    def loss(q, k, v, w):
        out = flash_attention(q, k, v, causal=True, impl="pallas",
                              window=window, heads=32)
        return (out.astype(jnp.float32) * w).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2)) if backward else loss
    text = jax.jit(fn).lower(q, kv, kv, w).compile().as_text()
    name = "flash_fwd" if window is None else f"flash_fwd_w{window}"
    assert f"%{name}" in text and "tpu_custom_call" in text
    copied = collections.Counter(re.findall(
        r"= (\w+\[[\d,]+\])\S* (?:copy|transpose)\(", text))
    # q's fold (the argument's own: a projection writes that layout), K^T
    # and V^T of four heads, and in the backward dQ's way back: nothing of
    # O, dO, dK or dV
    q_or_dq = {"bf16[1,16384,4096]", "bf16[1,16384,32,128]",
               "bf16[32,128,16384]", "bf16[32,16384,128]"}
    assert set(copied) <= q_or_dq | {"bf16[4,128,16384]",
                                     "bf16[4,16384,128]"}, copied
    assert sum(copied[shape] for shape in q_or_dq) <= (4 if backward else 2)
    if backward:
        assert not re.search(r" reduce\(", text)
        handed, = re.findall(r"%flash_bwd\S* = (.*?) custom-call\(", text)
        assert re.findall(r"(\w+\[[\d,]+\])", handed) == [
            "f32[32,128,16384]", "bf16[1,16384,512]", "bf16[1,16384,512]"]


@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "fwd_bwd"])
def test_causal_conv_kernels_compile(topo, no_compile_cache, backward):
    """The linear mixers' call in ``qwen3-next-80b-a3b.step-8k`` (PR 57): two
    sequences of 8,192 positions of 8,192 channels in bfloat16, four taps and
    a SiLU. One ``causal_conv_fwd`` and, in the gradient, one
    ``causal_conv_bwd`` (whose own forward is not run: the backward makes the
    sum again), blocks of 2,048 positions of 256 channels; nothing of the
    XLA form's passes is left beside them: no pad, no slice, no reduction
    over the positions."""
    from ray_tpu.ops import conv

    one_chip = SingleDeviceSharding(topo.devices[0])
    x = jax.ShapeDtypeStruct((2, 8192, 8192), jnp.bfloat16, sharding=one_chip)
    taps = jax.ShapeDtypeStruct((4, 8192), jnp.float32, sharding=one_chip)
    assert conv.block_rows(8192, conv._slab(8192), 2) == 2048

    def fwd(x, taps):
        return conv.causal_conv(x, taps, jax.nn.silu, impl="pallas")

    def grads(x, taps, dy):
        return jax.vjp(fwd, x, taps)[1](dy)

    args = (x, taps, x) if backward else (x, taps)
    text = jax.jit(grads if backward else fwd).lower(*args).compile().as_text()
    calls = collections.Counter(re.findall(
        r"(causal_conv_(?:fwd|bwd))[\w.\-]* = .*"
        r'custom_call_target="tpu_custom_call"', text))
    assert calls == ({"causal_conv_bwd": 1} if backward
                     else {"causal_conv_fwd": 1})
    assert not re.findall(r" (?:pad|slice|copy|transpose)\(", text)
    # the taps' gradient: two sequences' float32 partial sums added
    assert len(re.findall(r" reduce\(", text)) <= (1 if backward else 0)


@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "fwd_bwd"])
def test_group_norm_kernels_compile(topo, no_compile_cache, backward):
    """A Mamba-2 block's gated, grouped norm in
    ``nemotron-3-nano-30b-a3b.step-8k`` (PR 64): two sequences of 8,192
    tokens of 4,096 channels in 8 groups, bfloat16. One ``group_norm_fwd``
    and, in the gradient, one ``group_norm_bwd`` (whose own forward is not
    run: the backward makes the statistic again), blocks of 256 and of 128
    tokens with all 4,096 lanes; beside them nothing of the XLA form's
    passes: no copy into the groups' view, no broadcast out of it, nothing
    float32 the size of the operands."""
    from ray_tpu.ops import norm

    one_chip = SingleDeviceSharding(topo.devices[0])
    x = jax.ShapeDtypeStruct((2, 8192, 4096), jnp.bfloat16, sharding=one_chip)
    scale = jax.ShapeDtypeStruct((4096,), jnp.float32, sharding=one_chip)
    assert norm.fits(x, 8)
    assert norm.block_tokens(8192, 4096, 2, backward) == (
        128 if backward else 256)

    def fwd(y, z, scale):
        return norm.gated_group_rms_norm(y, z, scale, groups=8, eps=1e-5,
                                         impl="pallas")

    def grads(y, z, scale, do):
        return jax.vjp(fwd, y, z, scale)[1](do)

    args = (x, x, scale, x) if backward else (x, x, scale)
    text = jax.jit(grads if backward else fwd).lower(*args).compile().as_text()
    calls = collections.Counter(re.findall(
        r"(group_norm_(?:fwd|bwd))[\w.\-]* = .*"
        r'custom_call_target="tpu_custom_call"', text))
    assert calls == ({"group_norm_bwd": 1} if backward
                     else {"group_norm_fwd": 1})
    assert not re.findall(r" (?:copy|transpose|broadcast|convert)\(", text)
    assert not re.search(r"f32\[2,8192,", text)
    # the scale's gradient: 128 grid steps' float32 partial sums added
    assert len(re.findall(r" reduce\(", text)) <= (1 if backward else 0)


_FLASH_CALL = re.compile(r"^\s*%?(flash_fwd|flash_bwd)[\w.\-]* = .*"
                         r'custom_call_target="tpu_custom_call"', re.M)
_HEAD_SHAPED_COPY = re.compile(
    r"= bf16\[(?:16,12,1024,64|192,1024,64|192,64,1024|16,1024,12,64"
    r"|48,64,1024)\]\S* (?:copy|copy-done|slice-done)\(")


def _compile_step(cache, key, step, args):
    """(HLO text, planned bytes) of ``step`` compiled for ``args``. Two
    attention settings that lower to one program (``auto`` and ``flash`` at
    this shape on a TPU) are compiled once: ``cache`` is keyed by the
    lowered text."""
    lowered = step.lower(*args)
    program = (key, lowered.as_text())
    if program not in cache:
        compiled = lowered.compile()
        cache[program] = (compiled.as_text(), _device_bytes(compiled))
    return cache[program]


@pytest.fixture(scope="module")
def compiled_steps():
    return {}


def _one_chip(compiled_steps, topo, attention):
    one = SingleDeviceSharding(topo.devices[0])
    return _compile_step(compiled_steps, "one chip",
                         *_train_step_args(attention, one, one))


@pytest.mark.parametrize("attention", ["xla", "auto", "flash"])
def test_train_step_fits_one_chip(topo, no_compile_cache, compiled_steps,
                                  on_tpu, attention):
    """``auto`` at GPT-2's geometry is the kernel's program: it holds the
    Mosaic call and plans less HBM than ``xla``, whose saved score tensors
    it does not have; ``xla`` holds no kernel."""
    text, planned = _one_chip(compiled_steps, topo, attention)
    assert ("tpu_custom_call" in text) == (attention != "xla")
    assert planned < HBM_BYTES
    if attention == "auto":
        assert planned < _one_chip(compiled_steps, topo, "xla")[1]
        one = SingleDeviceSharding(topo.devices[0])
        step, args = _train_step_args(attention, one, one)
        _lower_held("gpt2-124m.step", step, *args)
    if attention != "xla":
        # the kernels address the model's [16, 1024, 12 x 64] themselves
        # (PR 51): 12 + 12 calls and none of the head-shaped copies that
        # stood round them (84 ``copy``, 12 ``copy-done``, 48 ``slice-done``
        # a step, each result lane-padded to twice its bytes), and the plan
        # that went with them: 4.80 GiB where it was 6.49
        assert collections.Counter(_FLASH_CALL.findall(text)) == {
            "flash_fwd": 12, "flash_bwd": 12}
        assert not _HEAD_SHAPED_COPY.findall(text)
        print(f"planned {planned / 2**30:.3f} GiB")
        assert planned < 5.25 * 2**30


@pytest.mark.parametrize("attention", ["xla", "auto", "flash"])
def test_train_step_data_parallel_4_chips(topo, no_compile_cache,
                                          compiled_steps, on_tpu, attention):
    """Global batch 64 over data=4, state replicated: the partitioner must
    add the gradient all-reduce, and — for the kernel — must be handed the
    Mosaic call already split per batch shard."""
    mesh = Mesh(np.array(topo.devices).reshape(4), ("data",))
    text, planned = _compile_step(compiled_steps, "data=4", *_train_step_args(
        attention, NamedSharding(mesh, PartitionSpec()),
        NamedSharding(mesh, PartitionSpec("data")), batch=4 * BATCH))
    assert "all-reduce" in text
    assert ("tpu_custom_call" in text) == (attention != "xla")
    assert planned < HBM_BYTES


def _placed_state(params, opt_state, p_sh):
    """Abstract params and optimizer state laid out as the
    ``shard_train_state*`` family places real ones."""
    return jax.tree.map(
        lambda s, h: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=h),
        (params, opt_state), state_shardings(params, opt_state, p_sh))


@pytest.mark.parametrize("attention", ["xla", "auto"])
def test_train_step_tensor_parallel_2x2(topo, no_compile_cache,
                                        compiled_steps, on_tpu, attention):
    """data=2 x model=2, ``shard_params_tp``: ``flash_attention`` maps only
    the batch axes, so under a ``model`` axis the Mosaic call would reach
    the partitioner, which refuses it (ROADMAP Speed 9a). ``auto`` sees the
    mesh in its operand's type and stays on XLA's attention: the program it
    compiled to before the kernel was chosen anywhere."""
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    step, (params, opt_state, batch) = _train_step_args(
        attention, NamedSharding(mesh, PartitionSpec()),
        NamedSharding(mesh, PartitionSpec("data")), batch=2 * BATCH)
    params, opt_state = _placed_state(
        params, opt_state, gpt2.shard_params_tp(params, mesh))
    text, planned = _compile_step(compiled_steps, "data=2 x model=2", step,
                                  (params, opt_state, batch))
    assert "all-reduce" in text
    assert "tpu_custom_call" not in text
    assert planned < HBM_BYTES


# ---------------------------------------------------------------------------
# GPT-2 XL under fsdp=4: the cell gpt2-xl.step-fsdp4 as the benchmark runs it
# ---------------------------------------------------------------------------

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_COLLECTIVE = re.compile(
    r"^\s*(?:ROOT )?%?[\w.\-]+ = (\(?.*?\)?) "
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(.*?channel_id=(\d+)")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$")
_ASYNC_START = re.compile(
    r"^\s*%async-collective-start[\w.\-]* = .* calls=%([\w.\-]+)")


def _census(text):
    """({kind: {channel: result type}}, kinds inside asynchronous pairs) of
    the collectives in compiled TPU HLO text, one entry a channel: the
    pieces of an asynchronous collective share theirs. The TPU compiler
    writes a reduce-scatter as a fusion that calls a computation named
    ``all-reduce-scatter`` (an all-reduce and a dynamic-slice inside), so
    an all-reduce found in such a computation is a reduce-scatter; and an
    overlapped collective as ``async-collective-start`` / ``-done``, whose
    kind only the computation it calls says."""
    kinds = collections.defaultdict(dict)
    bodies, name = collections.defaultdict(list), ""
    for line in text.splitlines():
        header = _COMPUTATION.match(line)
        if header:
            name = header.group(1)
            continue
        bodies[name].append(line)
        m = _COLLECTIVE.match(line)
        if m:
            kind = m.group(2)
            if kind == "all-reduce" and name.startswith("all-reduce-scatter"):
                kind = "reduce-scatter"
            kinds[kind].setdefault(int(m.group(3)), m.group(1))
    in_async = collections.Counter()
    for lines in list(bodies.values()):
        for line in lines:
            start = _ASYNC_START.match(line)
            if start:
                in_async.update({m.group(2) for m in map(
                    _COLLECTIVE.match, bodies[start.group(1)]) if m})
    return kinds, in_async


def _xl_cell():
    """The cell's configuration and traffic as ``perfbench/run.py`` finds
    them by name in BENCHMARK.json."""
    from perfbench import run

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        loaded = run.load_cell(json.load(f), "gpt2-xl.step-fsdp4")
    return loaded["model"], loaded["traffic"]


def test_gpt2_xl_fsdp4_step_is_zero3(topo, no_compile_cache, on_tpu):
    """GPT-2 XL, all 48 layers, global batch 64 x 1024 over fsdp=4, state
    placed as ``shard_train_state(..., fsdp=True)`` places it, on the mesh
    ``create_mesh`` builds on such a host (``create_device_mesh``: the
    chips in ring order, which is what lets the compiler walk a weight's
    shards round the ring, each beside its part of the matmul). What the
    compiled step must show: it fits a chip with room; its state leaves in
    the shardings it came in (one compilation); nothing that moves between
    chips is shaped like an activation, a score or a logit: all-gathers and
    collective-permutes carry weights and their shards, the reduce-scatter
    fusions and all-reduces carry gradients (the embeddings', and the
    vectors that are replicated at rest: no weight matrix is all-reduced);
    nothing is resharded by all-to-all; attention is the Pallas kernel per
    batch shard, 96 calls with the recomputation (a recomputed block keeps
    the kernel's output and log-sum-exp, ``ops.remat.remat_policy``,
    and runs no forward call again: the kept copies are 4.4 GiB of the
    plan, which the upper limit holds 1.5 GiB under the chip); and every
    asynchronous collective is an all-gather, which
    perfbench/metrics/allgather_ms.py relies on."""
    from jax.experimental import mesh_utils as jmu

    from ray_tpu.parallel import mesh_utils

    model_cfg, traffic = _xl_cell()
    layers, width = model_cfg["n_layer"], model_cfg["n_embd"]
    mesh = Mesh(jmu.create_device_mesh([4], devices=topo.devices), ("fsdp",))
    config = gpt2.GPT2Config(
        vocab_size=model_cfg["vocab_size"],
        n_positions=model_cfg["n_positions"], n_embd=width, n_layer=layers,
        n_head=model_cfg["n_head"], dtype=jnp.bfloat16,
        remat=traffic["remat"], attention=model_cfg["train"]["attention"],
        loss_chunks=model_cfg["train"]["loss_chunks"])
    model, tx = gpt2.GPT2(config), gpt2.make_optimizer()

    def state(rng):
        params = gpt2.init_params(config, rng)[1]
        return params, tx.init(params)

    params, opt_state = jax.eval_shape(state, jax.random.PRNGKey(0))
    params, opt_state = _placed_state(
        params, opt_state, mesh_utils.shard_params_fsdp(params, mesh))
    ids = jax.ShapeDtypeStruct((traffic["batch"], traffic["seq"]), jnp.int32,
                               sharding=mesh_utils.data_sharding(mesh))
    compiled = _lower_held(
        "gpt2-xl.step-fsdp4", gpt2.build_train_step(model, tx, donate=True),
        params, opt_state, {"input_ids": ids, "labels": ids}).compile()

    planned = _device_bytes(compiled)
    print(f"planned {planned / 2**30:.3f} GiB", compiled.memory_analysis())
    assert 0.5 * 15.75 * 2**30 < planned < 14.25 * 2**30
    out_params, out_opt_state, _ = compiled.output_shardings
    for out, arg in zip(jax.tree.leaves((out_params, out_opt_state)),
                        jax.tree.leaves((params, opt_state))):
        assert out.is_equivalent_to(arg.sharding, len(arg.shape))

    text = compiled.as_text()
    kinds, in_async = _census(text)
    assert not kinds["all-to-all"]
    # a chip's batch or the whole one leading, then sequence, loss chunk or
    # heads: what an activation, a score or a logit looks like
    batch = traffic["batch"]
    activation = re.compile(
        rf"\[(?:{batch}|{batch // 4}),"
        rf"(?:{traffic['seq']}|{traffic['seq'] // 8}|{model_cfg['n_head']}),")
    for kind, found in kinds.items():
        for shape in found.values():
            assert not activation.search(shape), (kind, shape)
    # each layer's four matrices are gathered, whole or shard by shard
    assert len(kinds["all-gather"]) >= 2 * layers
    assert len(kinds["all-gather"]) + len(kinds["collective-permute"]) \
        >= 2 * 4 * layers
    assert set(in_async) == {"all-gather"}
    # gradients are scattered as they are summed: the embeddings' by the
    # fusion, a matrix's as its shards (a quarter of the rows) go round
    # the ring; what is all-reduced is vectors
    assert kinds["reduce-scatter"]
    quarter = f"bf16[{width // 4},{3 * width}]"
    assert any(quarter in shape
               for shape in kinds["collective-permute"].values())
    assert 1 <= len(kinds["all-reduce"]) <= 8
    for shape in kinds["all-reduce"].values():
        assert not re.search(r"\[\d+,\d+", shape), shape
    assert not re.search(r"f32\[\d+,25,1024,1024\]", text)
    assert collections.Counter(_FLASH_CALL.findall(text)) == {
        "flash_fwd": layers, "flash_bwd": layers}
    # a chip's share of the batch, in the model's own [B, T, H x d]: the
    # kernels address it (25 heads: twelve lane tiles and half a one)
    written = re.findall(r"^\s*%?flash_fwd[\w.\-]* = \((\w+\[[\d,]+\])[^=]*? "
                         r"(\w+\[[\d,]+\])", text, re.M)
    assert set(written) == {("bf16[16,1024,1600]", "f32[16,13,2,1024]")}


def _row_buffer_census(text, drawn, pairs, d, width, unwritten):
    """The held experts' walked buffers in a compiled step ``text`` and in
    the ``counters`` events ``drawn`` while it was traced: the entry
    computation takes ``unwritten`` of them from a kernel that writes
    nothing (``ops.moe._unwritten``), fills none with a constant and copies
    none (a ``broadcast`` or a ``copy`` whose result is [pairs, a width of
    the layer] bfloat16: 2.25 to 3.25 GiB a layer a step until PR 46), each
    traced pass of the layer wrote one ``moe/row_buffers`` record: two
    buffers forward, five backward with the float32 vector, all of them
    unwritten."""
    entry = text[text.index("\nENTRY "):]
    assert not re.findall(
        rf"= bf16\[{pairs},(?:{d}|{width}|{2 * width})\]\S* "
        r"(?:broadcast|copy)\(", entry)
    records = {tuple(sorted(e["args"].items())) for e in drawn
               if e["name"] == "moe/row_buffers"}
    assert records == {tuple(sorted(want.items())) for want in (
        {"buffers": 2, "rows": pairs, "bytes": 2 * pairs * (d + width),
         "unwritten": 2, "backward": 0},
        {"buffers": 5, "rows": pairs, "unwritten": 5, "backward": 1,
         "bytes": 2 * pairs * (2 * d + 3 * width) + 4 * pairs})}
    assert len(re.findall(
        rf"%unwritten[\w.]* = (?:bf16\[{pairs},\d+\]|f32\[{pairs}\])\S* "
        r"custom-call\(\)", entry)) == unwritten


def _to_tokens_census(text, drawn, tokens, k, d, held, calls, block=512):
    """The expert layers' way back to the tokens in a compiled step ``text``
    (PR 53): ``calls`` calls of the kernel ``to_tokens``, one a pass of a
    layer that the executable keeps; no branch over cut gather sources; no
    ``gather`` and no ``reduce`` that forms or reads a [tokens x k, rows'
    width] or [tokens, k, rows' width] array (whole rows, or the halves and
    quarters of their columns that the gathers were cut into); and each
    traced pass wrote one ``moe/to_tokens`` record that says ``kernel``."""
    assert len(re.findall(
        r'^\s*%?to_tokens[\w.\-]* = .*custom_call_target="tpu_custom_call"',
        text, re.M)) == calls
    assert "conditional(" not in text
    widths = "|".join(str(d // parts) for parts in (1, 2, 4))
    slots = re.compile(rf"\[(?:{tokens * k}|{tokens},{k}),(?:{widths})\]")
    assert not [line for line in text.splitlines()
                if re.search(r" (?:gather|reduce)\(", line)
                and slots.search(line)]
    records = {tuple(sorted(e["args"].items())) for e in drawn
               if e["name"] == "moe/to_tokens"}
    assert records == {tuple(sorted({
        "kernel": 1, "slots": tokens * k, "tokens": tokens, "block": block,
        "chunk": 128, "held": held, "backward": backward}.items()))
        for backward in (0, 1)}


def _grouped_matmul_census(text, drawn, pairs, d, up, width, held, layers,
                           recomputed: int = 0):
    """The held experts' grouped matmuls in a compiled step ``text`` (PR 61):
    none is the compiler's ``ragged-dot-none``; each expert layer calls the
    kernels of ``ops/grouped_matmul.py`` seven times (hidden and out
    forward; hidden again, the two operands' gradients and the two
    matrices' backward; ``recomputed``: the forward's two again where the
    recomputed forward is live), five over buffers ``pairs`` long and two
    whose result has the held experts' leading dimension; and each traced
    matmul wrote one ``moe/grouped_matmul``
    record that says ``kernel``, with the tiles ``tiles_by_group`` /
    ``tiles_per_group`` choose for its shapes."""
    from ray_tpu.ops import grouped_matmul

    assert "ragged-dot" not in text
    calls = collections.Counter(re.findall(
        r"^\s*%?(grouped_matmul_\w+?)[.\d]* = (\w+\[\d+),.*"
        r'custom_call_target="tpu_custom_call"', text, re.M))
    assert calls == {
        ("grouped_matmul_rows", f"bf16[{pairs}"): (3 + recomputed) * layers,
        ("grouped_matmul_rows_t", f"bf16[{pairs}"): 2 * layers,
        ("grouped_matmul_matrices", f"bf16[{held}"): 2 * layers}, calls
    bf16 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.bfloat16)
    want = set()
    for form, k, n, backward in (
            (0, d, up, 0), (0, width, d, 0), (0, d, up, 1), (1, d, width, 1),
            (1, up, d, 1), (2, d, up, 1), (2, width, d, 1)):
        if form == 2:
            tile, block_k, block_n = grouped_matmul.tiles_per_group(
                bf16((pairs, k)), bf16((pairs, n)))
        else:
            tile, block_n = grouped_matmul.tiles_by_group(
                bf16((pairs, k)),
                bf16((held, n, k) if form else (held, k, n)), bool(form))
            block_k = k
        want.add(tuple(sorted({
            "kernel": 1, "form": form, "rows": pairs, "k": k, "n": n,
            "held": held, "tile": tile, "block_k": block_k,
            "block_n": block_n, "backward": backward}.items())))
    assert {tuple(sorted(e["args"].items())) for e in drawn
            if e["name"] == "moe/grouped_matmul"} == want


def _cut_cell(name="joyai-llm-flash.step-8k"):
    from perfbench import run, worker

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        loaded = run.load_cell(json.load(f), name)
    return worker, loaded["model"], loaded["traffic"]


def _dq_census(text, heads, d, seq):
    """Since PR 50 the backward kernel sums dQ^T over a head's blocks of
    keys itself: each ``flash_bwd*`` call of the compiled step hands back
    one float32 [B x H, d, T] array, and no array of the program has an
    axis of partials before those three (until then ``f32[n, B x H, d,
    T]``, one a block of keys, which a reduction after the call summed)."""
    assert f"f32[{heads},{d},{seq}]" in text
    assert not re.search(rf"f32\[\d+,{heads},{d},{seq}\]", text)


def _windowed_calls_census(text, heads, kv_heads, seq, window, layers):
    """The benchmark's readers find a windowed call by its instruction's
    name and take its shapes from the FIRST THREE operands of its
    ``custom-call`` (``attn_masked_roofline_pct.needed_flops``): q [B x H,
    T, 128], k [B x H_kv, T, 128], then V^T (forward) or V (backward), all
    three-dimensional. The grid that holds a row's live blocks alone (PR
    66) is index maps' arithmetic over the same operands: no table of
    scalars stands before them, and the reader counts each call's needed
    pairs as it did."""
    from perfbench.metrics.attn_masked_roofline_pct import (attended_pairs,
                                                            needed_flops)

    lines = [line.strip() for line in text.splitlines() if re.match(
        rf'\s*%?flash_(?:fwd|bwd)_w{window}[\w.\-]* = .*'
        r'custom_call_target="tpu_custom_call"', line)]
    assert len(lines) == 2 * layers
    pairs = heads * attended_pairs(seq, seq, window)
    needed = {"fwd": pairs * 2 * (128 + 128),
              "bwd": pairs * 2 * (3 * 128 + 2 * 128)}
    first_three = {
        "fwd": [(heads, seq, 128), (kv_heads, seq, 128), (kv_heads, 128, seq)],
        "bwd": [(heads, seq, 128), (kv_heads, seq, 128), (kv_heads, seq, 128)]}
    for line in lines:
        kind = re.match(r"%?flash_(fwd|bwd)", line).group(1)
        # a trace's event prints each operand with its shape; the compiled
        # text names the operands alone and says their shapes, in their
        # order, as the call's layout constraints
        names = re.search(r"custom-call\((.*?)\), custom_call_target=",
                          line).group(1)
        shapes = re.findall(r"\w+\[[\d,]*\]\{[\d,]*\}", re.search(
            r"operand_layout_constraints=\{((?:\w+\[[\d,]*\]\{[\d,]*\}"
            r"(?:, )?)+)\}", line).group(1))
        assert len(shapes) == len(names.split(", ")), (shapes, names)
        assert not any(shape.startswith("s32") for shape in shapes), shapes
        assert [tuple(int(n) for n in re.search(
            r"\[([\d,]*)\]", shape).group(1).split(","))
                for shape in shapes[:3]] == first_three[kind], shapes
        event = line.replace(f"custom-call({names})", "custom-call(" + ", ".join(
            f"{shape} {name}"
            for shape, name in zip(shapes, names.split(", "))) + ")")
        assert needed_flops(event) == needed[kind], event[:400]


def _q_sized_census(text, elements):
    """{(kind, result): instructions} of the compiled step, outside fused
    computations, whose result (or one of a tuple's) is a float32 or
    bfloat16 array of ``elements`` entries, a layer's q: a fusion by its
    name's kind (``pad_maximum_fusion``), any other by its operation
    (``copy``, ``custom-call``). At the parent of PR 63 the mellum2 cell's
    step held 76 such (12 ``copy f32[2,8192,32,128]``, 8 ``fusion`` and 8
    ``broadcast`` of that result, 8 ``pad_maximum_fusion``, 4 ``copy
    f32[2,32,8192,128]``, 4 ``convert_convert_fusion f32[64,128,8192]``, the
    projections' results widened to float32) and the window cell's 102:
    the heads' norm and the rotation as XLA made them (PERF.md section 6)."""
    found, fused = collections.Counter(), False
    for line in text.splitlines():
        if line and not line[0].isspace():    # a computation's first line
            fused = line.endswith("{") and bool(
                re.match(r"%?(fused_computation|region)", line))
            continue
        m = re.match(r"\s+(?:ROOT )?%?([\w.\-]+) = (.*?) ([\w\-]+)\(", line)
        if fused or not m or m.group(3) in (
                "parameter", "bitcast", "get-tuple-element", "tuple",
                "constant"):
            continue
        name, result, op = m.groups()
        for dtype, dims in re.findall(r"\b(f32|bf16)\[([\d,]+)\]", result):
            if np.prod([int(n) for n in dims.split(",")]) == elements:
                kind = re.sub(r"[.\d]+$", "", name) if op == "fusion" else op
                found[kind, f"{dtype}[{dims}]"] += 1
    return found


def _head_rotary_census(text, counters, q_elements, layers, rotated):
    """Since PR 63 the heads' norm and the rotation between the q / k
    projections and the flash kernels are ``ops/rotary.py``'s kernel pair:
    in the compiled step ``head_rotary_fwd`` for q and for k a layer, run
    and recomputed (nothing of it is named for ``remat_policy``), and
    ``head_rotary_bwd`` once for each; every traced layer said so
    (``attention/head_rotary``: ``kernel`` 1, ``rotated`` in ``rotated`` of
    them); and between a projection and a flash call XLA makes nothing of
    q's size: no ``copy``, ``convert`` or ``transpose`` of that size in
    either type, and nothing float32 of it but the flash backward's own
    dQ^T sum, which the backward kernel reads as it is."""
    said = [e["args"] for e in counters
            if e["name"] == "attention/head_rotary"]
    assert [e["kernel"] for e in said] == [1] * len(said), said
    assert {(e["heads"], e["kv_heads"], e["head_dim"])
            for e in said} == {(32, 4, 128)}
    assert len(said) % layers == 0 and (
        sum(e["rotated"] for e in said) * layers == rotated * len(said))
    calls = collections.Counter(re.findall(
        r"^\s*%?(head_rotary_(?:fwd|bwd))[\w.\-]* = .*"
        r'custom_call_target="tpu_custom_call"', text, re.M))
    assert calls == {"head_rotary_fwd": 4 * layers,
                     "head_rotary_bwd": 2 * layers}
    census = _q_sized_census(text, q_elements)
    print("results of q's size:", dict(census))
    assert {kind for kind, result in census
            if result.startswith("f32")} == {"custom-call"}, census
    assert sum(n for (_, result), n in census.items()
               if result.startswith("f32")) == layers     # dQ^T sums
    assert not {kind for kind, _ in census} & {"copy", "convert",
                                               "transpose"}, census


def _group_norm_census(text, counters, blocks, batch, seq, width, groups):
    """Since PR 64 a Mamba-2 block's gated, grouped norm is ``ops/norm.py``'s
    kernel pair, forward, forward again in the recomputed block (nothing
    of it is named for ``remat_policy``) and backward: every traced pass
    said so (``norm/gated_group``: ``kernel`` 1, three records a block);
    and of the XLA form's passes nothing is left (ISSUE 64's
    table, 88 instructions at the parent): no result in the groups' float32
    view ([.., groups, W / groups], its tiles' [T / 8, groups, 8, W /
    groups]) nor the scan's output widened a head at a time, no ``copy``,
    ``reshape`` or ``broadcast`` that writes a float32 [B, T, W], and no
    ``copy`` of a [B, T, W] array in the compute type: the kept scan output
    goes into the recomputed block as the kernels wrote it, and the
    reshapes round the norm lower to nothing."""
    tokens = batch * seq
    said = [e["args"] for e in counters if e["name"] == "norm/gated_group"]
    assert said == [
        {"tokens": tokens, "width": width, "groups": groups,
         "bytes_needed": tokens * width * 2 * (5 if e["backward"] else 3),
         "backward": e["backward"], "kernel": 1} for e in said], said
    assert [e["backward"] for e in said].count(1) == blocks
    assert len(said) == 3 * blocks, said
    census = _q_sized_census(text, tokens * width)
    print("results of the norm's size:", dict(census))
    run = width // groups
    for kind, result in census:
        assert result not in (
            f"f32[{batch},{seq},{groups},{run}]",
            f"f32[{tokens // 8},{groups},8,{run}]",
            f"f32[{batch},{seq},64,64]"), (kind, result)
        if result.endswith(f"[{batch},{seq},{width}]"):
            assert kind != "copy" and (
                result.startswith("bf16")
                or kind not in ("reshape", "broadcast")), (kind, result)
    assert not re.search(rf"= f32\[{batch},{seq},{groups}\]", text)


def test_latent_attention_expert_step_fits_one_chip_at_8k(
        topo, no_compile_cache, on_tpu):
    """The cut configuration of the cell ``joyai-llm-flash.step-8k`` (layer
    0, four expert layers and the prediction module at the published
    widths, 16 of 256 experts held, an eighth of the vocabulary), its step
    at 2 x 8192 with recomputation, as the benchmark's family builds it:
    the plan stays under 13.75 of one v5e chip's 15.75 GiB with the state's
    7.6 GiB as arguments; attention is the Pallas kernel at keys 192 and
    values 128 wide (12 calls and not 18: six blocks, forward and backward,
    the recomputed blocks keeping the forward's output and log-sum-exp by
    ``ops.remat.remat_policy``: 0.76 GiB, with which the plan fell); the
    routed experts' grouped matmuls are the kernels of
    ``ops/grouped_matmul.py`` (PR 61; ``_grouped_matmul_census``) over a row
    buffer of which loops walk what holds the pairs present; and no array
    is shaped like a [tokens, experts, capacity] dispatch or a [T, T] score
    matrix, whole or a head's. Each traced kernel call wrote its grid's
    blocks by kind into the runtime's ring, where a timeline finds them:
    4 x 4 a head, none walked in a loop with traced bounds. The loops'
    buffers are nobody's to fill (``_row_buffer_census``)."""
    from ray_tpu._private import steptrace

    worker, model, traffic = _cut_cell()
    built = worker.load_family(ROOT, model).build(model, traffic, None)
    one = SingleDeviceSharding(topo.devices[0])
    params, opt_state = _with_sharding(
        jax.eval_shape(built.make_state, jax.random.PRNGKey(0)), one)
    batch, seq = traffic["batch"], traffic["seq"]
    ids = jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=one)
    steptrace.set_enabled(True)
    steptrace.reset()
    try:
        lowered = _lower_held(
            "joyai-llm-flash.step-8k", built.step, params, opt_state,
            {"input_ids": ids, "labels": ids})
        drawn = [e for e in steptrace.chrome_trace(steptrace.merge_records(
            steptrace.snapshot())) if e["ph"] == "C"]
    finally:
        steptrace.set_enabled(False)
    assert {e["name"] for e in drawn} == {"attn/grid_blocks",
                                          "moe/row_buffers",
                                          "moe/to_tokens",
                                          "moe/grouped_matmul",
                                          "attention/boundary"}
    # several blocks of keys a head: the boundary the kernels were measured
    # with, [B x H, T, d] operands made by XLA
    assert {e["args"]["model_arrays"] for e in drawn
            if e["name"] == "attention/boundary"} == {0}
    blocks = [e for e in drawn if e["name"] == "attn/grid_blocks"]
    assert {e["args"]["backward"] for e in blocks} == {0, 1}
    for e in blocks:
        assert e["args"] == {
            "whole": 6, "diagonal": 4, "trailing": 0, "dead": 6, "looped": 0,
            "queries": seq, "keys": seq, "backward": e["args"]["backward"],
            "window": 0, "heads": 64, "kv_heads": 64, "dq_partials": 0,
            "steps": 16, "dead_steps": 6}
    compiled = lowered.compile()
    planned = _device_bytes(compiled)
    state_bytes = 3 * 4 * sum(
        int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    assert state_bytes < planned < 13.75 * 2**30
    print(f"planned {planned / 2**30:.3f} GiB", compiled.memory_analysis())
    text = compiled.as_text()
    assert len(re.findall(r"%flash_fwd[\w.]* = ", text)) == 6
    assert len(re.findall(r"%flash_bwd[\w.]* = ", text)) == 6
    assert "bf16[64,8192,192]" in text and "bf16[64,8192,128]" in text
    _dq_census(text, 64, 192, seq)
    tokens, experts = batch * seq, model["n_routed_experts_published"]
    # Each of the five expert layers runs seven grouped matmuls over whole
    # row buffers tokens x k long (no pair is dropped): two forward; hidden
    # again, the two operands' gradients and the two matrices' in the
    # backward pass (the recomputed forward's are dead: the backward pass
    # recomputes its own rows). What is no matmul walks the buffers only as
    # far as the pairs present, in loops that stand once in the executable
    # (two forward, two backward), and the rows go back to the tokens
    # through the kernel ``to_tokens`` (one call forward, one backward),
    # which reads the rows that hold a pair.
    pairs = tokens * model["num_experts_per_tok"]
    rungs = moe.row_buffer_rungs(pairs)
    assert len(rungs) > 8 and rungs[-1] == pairs
    _to_tokens_census(text, drawn, tokens, model["num_experts_per_tok"],
                      model["hidden_size"], model["n_routed_experts"],
                      calls=2 * 5)
    walked = [line.split(" while(")[0] for line in text.splitlines()
              if " while(" in line and f"[{pairs}," in line.split(" while(")[0]]
    assert len(walked) == 4 * 5
    _grouped_matmul_census(
        text, drawn, pairs, model["hidden_size"],
        2 * model["moe_intermediate_size"], model["moe_intermediate_size"],
        model["n_routed_experts"], layers=5)
    # and start from buffers nobody filled: seven an expert layer
    _row_buffer_census(text, drawn, pairs, model["hidden_size"],
                       model["moe_intermediate_size"], unwritten=7 * 5)
    shapes = set(re.findall(r"\b[a-z]\w*\[([\d,]+)\]", text))
    # 32 heads x (128 + 128) is 8192 too: the keys' and values' projection
    # [batch, T, 8192] is the one array that may look like a score matrix
    kv_width = model["num_attention_heads"] * (
        model["qk_nope_head_dim"] + model["v_head_dim"])
    for dims in (tuple(int(n) for n in s.split(",")) for s in shapes):
        # scores: two dimensions of the sequence's length side by side
        if dims == (batch, seq, kv_width):
            continue
        assert not any(a == b == seq for a, b in zip(dims, dims[1:])), dims
        # a dense dispatch: tokens x experts x anything
        assert not (len(dims) >= 3 and tokens in dims
                    and experts in dims), dims


def test_window_and_full_attention_expert_step_fits_one_chip_at_16k(
        topo, no_compile_cache, on_tpu):
    """The cut configuration of the cell ``trinity-mini.step-16k`` (a dense
    layer and four expert layers at the published widths, three window
    layers to the one full layer among the four, 8 of 128 experts held, an
    eighth of the vocabulary), its step at 1 x 16,384 with recomputation, as
    the benchmark's family builds it: the plan stays under 13.75 of one v5e
    chip's 15.75 GiB with the state's 6.05 GB as arguments; attention is
    the Pallas kernel over 32 query heads and 4 key-value heads, whose keys
    and values go in as they are, [4, 16384, 128]: four windowed calls
    (``flash_*_w2048``) and one without a window, forward and backward, and
    no forward call again (``ops.remat.remat_policy``). Each traced call
    wrote its grid's blocks by kind into the runtime's ring: 8 x 8 a head,
    under the window 8 diagonal, 7 trailing, 49 dead, without it 28 whole,
    8 diagonal, 28 dead, none walked in a loop with traced bounds; the
    windowed calls' grids launch a row's trailing and diagonal block alone
    (PR 66: 16 steps a head, 1 dead; ``_windowed_calls_census``). Each
    backward call hands back one float32 dQ^T sum (``_dq_census``: until PR
    50 two partials a block of queries under the window and eight, 2 GiB,
    in the full layer). No array is shaped like a
    [T, T] score matrix. The expert layers' loops start from buffers that
    nobody filled (``_row_buffer_census``)."""
    from ray_tpu._private import steptrace

    worker, model, traffic = _cut_cell("trinity-mini.step-16k")
    built = worker.load_family(ROOT, model).build(model, traffic, None)
    one = SingleDeviceSharding(topo.devices[0])
    params, opt_state = _with_sharding(
        jax.eval_shape(built.make_state, jax.random.PRNGKey(0)), one)
    batch, seq = traffic["batch"], traffic["seq"]
    assert (batch, seq) == (1, 16384)
    ids = jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=one)
    steptrace.set_enabled(True)
    steptrace.reset()
    try:
        lowered = _lower_held(
            "trinity-mini.step-16k", built.step,
            params, opt_state, {"input_ids": ids, "labels": ids})
        counters = [e for e in steptrace.chrome_trace(
            steptrace.merge_records(steptrace.snapshot())) if e["ph"] == "C"]
        drawn = [e["args"] for e in counters
                 if e["name"] == "attn/grid_blocks"]
    finally:
        steptrace.set_enabled(False)
    # all five layers' calls, window or none: the kernels' results and the
    # output's cotangent cross in the model's arrays (PR 55), q, k and V^T
    # stay XLA's [B x H, T, d]
    boundary = [e["args"] for e in counters
                if e["name"] == "attention/boundary"]
    assert {(e["kv_heads"], e["model_arrays"], e["model_results"])
            for e in boundary} == {(4, 0, 1)}
    assert collections.Counter(e["window"] for e in boundary) == {
        2048: 4, 0: 1}    # 5 of the 5 layers
    # the windowed calls' grids hold a row's trailing and diagonal block
    # alone, 16 steps a head, one of them past the head's edge (PR 66);
    # the full layer's every block
    by_window = {2048: (0, 8, 7, 49, 16, 1), 0: (28, 8, 0, 28, 64, 28)}
    assert {(e["window"], e["backward"]) for e in drawn} == {
        (w, b) for w in by_window for b in (0, 1)}
    for e in drawn:
        whole, diagonal, trailing, dead, steps, dead_steps = by_window[
            e["window"]]
        assert e == {
            "whole": whole, "diagonal": diagonal, "trailing": trailing,
            "dead": dead, "looped": 0, "queries": seq, "keys": seq,
            "backward": e["backward"], "window": e["window"], "heads": 32,
            "kv_heads": 4, "dq_partials": 0, "steps": steps,
            "dead_steps": dead_steps}
    compiled = lowered.compile()
    planned = _device_bytes(compiled)
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    assert n_params == 504_147_712
    assert 3 * 4 * n_params < planned < 13.75 * 2**30
    assert planned > 4 * 2**30
    text = compiled.as_text()
    calls = collections.Counter(re.findall(
        r"^\s*%?(flash_(?:fwd|bwd)(?:_w\d+)?)[\w.\-]* = .*"
        r'custom_call_target="tpu_custom_call"', text, re.M))
    assert calls == {"flash_fwd": 1, "flash_bwd": 1, "flash_fwd_w2048": 4,
                     "flash_bwd_w2048": 4}
    assert "bf16[32,16384,128]" in text and "bf16[4,16384,128]" in text
    _windowed_calls_census(text, 32, 4, seq, 2048, layers=4)
    _dq_census(text, 32, 128, seq)
    _head_rotary_census(text, counters, seq * 32 * 128, layers=5, rotated=4)
    # round the ten calls XLA laid the kernel's output out three times and
    # its cotangent twice (PR 53's kept trace: 16 copies a step of
    # ``bf16[1,16384,32,128]``, 15 of ``bf16[16384,4096]`` and 5 of the 10
    # of ``bf16[32,128,16384]``, 14.6 ms of a 474 ms step); since PR 55 the
    # kernels write O, dK and dV into, and read O and dO from, the model's
    # [1, 16384, heads x 128] themselves. What is left of the third shape is
    # dQ's rounding, one a layer: the float32 sum leaves the kernel as it
    # did (the other copies of a head's shape, printed, are the backward of
    # the heads' norm and of the rotary halves, not the kernels' boundary)
    copies = collections.Counter(re.findall(
        r"= (\w+\[[\d,]+\])\S* copy\(", text))
    print("copies by result:", dict(copies))
    assert copies["bf16[1,16384,32,128]"] <= 1, copies   # a norm's own
    assert not copies["bf16[16384,4096]"], copies
    assert copies["bf16[32,128,16384]"] <= 5, copies
    for call, results in (("flash_fwd", ["bf16[1,16384,4096]"]),
                          ("flash_bwd", ["bf16[1,16384,512]"] * 2)):
        lines = [line for line in text.splitlines()
                 if re.match(rf"\s*%?{call}[\w.\-]* = ", line)]
        assert len(lines) == 5
        for line in lines:
            handed = re.findall(r"bf16\[[\d,]+\]",
                                line.split(" custom-call(")[0])
            assert handed == results, line[:400]
    shapes = set(re.findall(r"\b[a-z]\w*\[([\d,]+)\]", text))
    for dims in (tuple(int(n) for n in s.split(",")) for s in shapes):
        assert not any(a == b == seq for a, b in zip(dims, dims[1:])), dims
    # the four expert layers: two loops forward, two in the recomputed
    # forward (a block's last norm reads the layer's result, so here it is
    # not dead) and two backward, each over row buffers tokens x k long that
    # nobody filled (2 + 2 + 5 a layer), the way back to the tokens one
    # call of the kernel ``to_tokens`` in each of the three passes
    pairs = seq * model["num_experts_per_tok"]
    _to_tokens_census(text, counters, seq, model["num_experts_per_tok"],
                      model["hidden_size"], model["num_experts"],
                      calls=3 * 4)
    assert len([line for line in text.splitlines() if " while(" in line
                and f"[{pairs}," in line.split(" while(")[0]]) == 6 * 4
    _row_buffer_census(text, counters, pairs, model["hidden_size"],
                       model["moe_intermediate_size"], unwritten=9 * 4)
    _grouped_matmul_census(
        text, counters, pairs, model["hidden_size"],
        2 * model["moe_intermediate_size"], model["moe_intermediate_size"],
        model["num_experts"], layers=4, recomputed=2)
    # the vocabulary's slice equals no other dimension of the program: what
    # ``loss_head_ms`` reads as vocabulary-wide is the head and the embedding
    others = {model[k] for k in ("hidden_size", "intermediate_size",
                                 "moe_intermediate_size", "head_dim")}
    others |= {seq, seq * model["num_experts_per_tok"], 32 * 128, 4 * 128,
               model["num_experts_published"], 2 * 1024, 2 * 6144}
    assert model["vocab_size"] not in others
    print(f"planned {planned / 2**30:.3f} GiB", compiled.memory_analysis())


def test_five_kinds_step_fits_one_chip_at_one_16k_sequence(
        topo, no_compile_cache, on_tpu):
    """The cut configuration of the cell ``phi-4-mini-flash.step-one-seq``
    (layers 0, 1, 16, 17, 18 and 19 of the published 32 at the published
    widths, an eighth of the vocabulary), its step at 1 x 16,384 with
    recomputation, as the benchmark's family builds it: the plan stays under
    the 14.5 GiB that ISSUE 48 set for this length (11.55 read) with the
    state's 8.37 GB as arguments. The two state-space layers' scans are the
    Pallas kernels, once forward and once backward each
    (``ops.remat.remat_policy`` keeps the output and the boundary
    states); each of the three differential layers is two flash calls
    forward and two backward, 20 query heads on 10 key-value heads with keys
    64 and values 128 wide, the window layer's named after its 512 keys.
    Each traced call wrote its record into the runtime's ring: the scan's
    geometry, the 8 x 8 grid blocks a head (under the window all walked in
    loops: 512 keys are a quarter of a block), and the kinds of layer the
    model built. No array is shaped like a [T, T] score matrix or like every
    state of the recurrence ([T, channels, states])."""
    from ray_tpu._private import steptrace

    worker, model, traffic = _cut_cell("phi-4-mini-flash.step-one-seq")
    built = worker.load_family(ROOT, model).build(model, traffic, None)
    one = SingleDeviceSharding(topo.devices[0])
    params, opt_state = _with_sharding(
        jax.eval_shape(built.make_state, jax.random.PRNGKey(0)), one)
    batch, seq = traffic["batch"], traffic["seq"]
    assert (batch, seq) == (1, 16384)
    ids = jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=one)
    steptrace.set_enabled(True)
    steptrace.reset()
    try:
        lowered = _lower_held(
            "phi-4-mini-flash.step-one-seq", built.step, params, opt_state,
            {"input_ids": ids, "labels": ids})
        counters = [e for e in steptrace.chrome_trace(
            steptrace.merge_records(steptrace.snapshot())) if e["ph"] == "C"]
    finally:
        steptrace.set_enabled(False)
    by_name = collections.defaultdict(list)
    for e in counters:
        by_name[e["name"]].append(e["args"])
    assert set(by_name) == {"attn/grid_blocks", "ssm/scan",
                            "model/layer_kinds", "attention/boundary"}
    assert {(e["d_qk"], e["d_v"], e["model_arrays"])
            for e in by_name["attention/boundary"]} == {(64, 128, 0)}
    assert by_name["model/layer_kinds"][-1] == {
        "ssm": 2, "window": 1, "full": 1, "gmu": 1, "cross": 1, "layers": 6,
        "published_layers": 32, "hands_memory": 16, "hands_keys_values": 17}
    assert {e["backward"] for e in by_name["ssm/scan"]} == {0, 1}
    for e in by_name["ssm/scan"]:
        assert e == {"channels": 5120, "states": 16, "tokens": seq,
                     "chunk": 128, "chunks": 128, "backward": e["backward"],
                     "boundary_bytes": 128 * 16 * 5120 * 4}
    by_window = {512: (0, 0, 0, 0, 64), 0: (28, 8, 0, 28, 0)}
    assert {(e["window"], e["backward"])
            for e in by_name["attn/grid_blocks"]} == {
        (w, b) for w in by_window for b in (0, 1)}
    for e in by_name["attn/grid_blocks"]:
        whole, diagonal, trailing, dead, looped = by_window[e["window"]]
        assert e == {
            "whole": whole, "diagonal": diagonal, "trailing": trailing,
            "dead": dead, "looped": looped, "queries": seq, "keys": seq,
            "backward": e["backward"], "window": e["window"], "heads": 20,
            "kv_heads": 10, "dq_partials": 0, "steps": 64,
            "dead_steps": dead}
    compiled = lowered.compile()
    planned = _device_bytes(compiled)
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    assert n_params == 697_094_272
    assert 3 * 4 * n_params < planned < 14.5 * 2**30
    text = compiled.as_text()
    calls = collections.Counter(re.findall(
        r"^\s*%?((?:flash|ssm_scan)_(?:fwd|bwd)(?:_w\d+)?)[\w.\-]* = .*"
        r'custom_call_target="tpu_custom_call"', text, re.M))
    assert calls == {"flash_fwd": 4, "flash_bwd": 4, "flash_fwd_w512": 2,
                     "flash_bwd_w512": 2, "ssm_scan_fwd": 2,
                     "ssm_scan_bwd": 2}
    assert "bf16[20,16384,64]" in text and "bf16[10,16384,128]" in text
    _dq_census(text, 20, 64, seq)
    assert "f32[1,128,16,5120]" in text          # the boundary states
    shapes = set(re.findall(r"\b[a-z]\w*\[([\d,]+)\]", text))
    for dims in (tuple(int(n) for n in s.split(",")) for s in shapes):
        assert not any(a == b == seq for a, b in zip(dims, dims[1:])), dims
        assert not (seq in dims and 5120 in dims and 16 in dims), dims
    # the vocabulary's slice equals no other dimension of the program: what
    # ``loss_head_ms`` reads as vocabulary-wide is the head and the embedding
    others = {model[k] for k in ("hidden_size", "intermediate_size",
                                 "sliding_window", "dt_rank")}
    others |= {seq, seq * 16, 5120, 2 * 5120, 2 * 10240, 1280, 160 + 32,
               20 * 128, 2048}
    assert model["vocab_size"] == 25008 and 25008 not in others
    print(f"planned {planned / 2**30:.3f} GiB", compiled.memory_analysis())


def test_short_convolution_expert_step_fits_one_chip_at_four_8k_sequences(
        topo, no_compile_cache, on_tpu):
    """The cut configuration of the cell ``lfm2-8b-a1b.step-8k`` (published
    layers 0, 2, 3, 4 and 5 at the published widths: four gated
    short-convolution layers and one grouped-attention layer, a dense
    feed-forward and four expert layers of 8 of 32 experts with no shared
    one, a quarter of the vocabulary under a tied head), its step at 4 x
    8,192 with recomputation, as the benchmark's family builds it: the plan
    stays under the 14.5 GiB that ISSUE 52 set for this batch (13.60 read)
    with the state's 6.09 GB as arguments. Every convolution is the Pallas
    kernel pair over the in-projection's own [4, 8192, 6144] array: two
    forward calls a layer (the recomputed block makes the output again:
    nothing of it is kept) and one backward; attention is one flash call
    forward and one backward, 32 query heads on 8 key-value heads of 64,
    whose output ``ops.remat.remat_policy`` keeps. Each traced call
    wrote its record into the runtime's ring. No array is shaped like a [T,
    T] score matrix, and the vocabulary's 16,384 rows equal no other
    dimension of the program."""
    from ray_tpu._private import steptrace

    worker, model, traffic = _cut_cell("lfm2-8b-a1b.step-8k")
    built = worker.load_family(ROOT, model).build(model, traffic, None)
    one = SingleDeviceSharding(topo.devices[0])
    params, opt_state = _with_sharding(
        jax.eval_shape(built.make_state, jax.random.PRNGKey(0)), one)
    batch, seq = traffic["batch"], traffic["seq"]
    assert (batch, seq) == (4, 8192)
    ids = jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=one)
    steptrace.set_enabled(True)
    steptrace.reset()
    try:
        lowered = _lower_held(
            "lfm2-8b-a1b.step-8k", built.step, params, opt_state,
            {"input_ids": ids, "labels": ids})
        counters = [e for e in steptrace.chrome_trace(
            steptrace.merge_records(steptrace.snapshot())) if e["ph"] == "C"]
    finally:
        steptrace.set_enabled(False)
    by_name = collections.defaultdict(list)
    for e in counters:
        by_name[e["name"]].append(e["args"])
    assert set(by_name) == {"attn/grid_blocks", "conv/short",
                            "model/layer_kinds", "attention/boundary",
                            "moe/row_buffers", "moe/to_tokens",
                            "moe/grouped_matmul"}
    assert by_name["model/layer_kinds"][-1] == {
        "conv": 4, "full_attention": 1, "dense": 1, "expert": 4, "layers": 5,
        "published_layers": 24}
    assert {(e["heads"], e["kv_heads"], e["d_qk"], e["d_v"],
             e["model_arrays"]) for e in by_name["attention/boundary"]} == {
        (32, 8, 64, 64, 0)}
    assert {e["backward"] for e in by_name["conv/short"]} == {0, 1}
    cells = batch * seq * 2048 * 2
    for e in by_name["conv/short"]:
        assert e == {"channels": 2048, "taps": 3, "tokens": batch * seq,
                     "sequences": batch, "backward": e["backward"],
                     "bytes_needed": (7 * cells + 2 * 3 * 2048 * 4
                                      if e["backward"]
                                      else 4 * cells + 3 * 2048 * 4)}
    compiled = lowered.compile()
    planned = _device_bytes(compiled)
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    assert n_params == 507_820_288
    assert 3 * 4 * n_params < planned < 14.5 * 2**30
    text = compiled.as_text()
    calls = collections.Counter(re.findall(
        r"^\s*%?((?:flash|short_conv)_(?:fwd|bwd)(?:_w\d+)?)[\w.\-]* = .*"
        r'custom_call_target="tpu_custom_call"', text, re.M))
    assert calls == {"flash_fwd": 1, "flash_bwd": 1, "short_conv_fwd": 8,
                     "short_conv_bwd": 4}
    assert "bf16[128,8192,64]" in text and "bf16[32,8192,64]" in text
    assert "bf16[4,8192,6144]" in text
    # the four expert layers' rows go back to the tokens through the kernel
    # ``to_tokens``, forward and backward (no recomputed forward of the
    # layer is live): 8 calls where 8 branches of gathers stood
    _to_tokens_census(text, counters, batch * seq,
                      model["num_experts_per_tok"], model["hidden_size"],
                      model["num_experts"], calls=2 * 4)
    _grouped_matmul_census(
        text, counters, batch * seq * model["num_experts_per_tok"],
        model["hidden_size"], 2 * model["moe_intermediate_size"],
        model["moe_intermediate_size"], model["num_experts"], layers=4)
    shapes = set(re.findall(r"\b[a-z]\w*\[([\d,]+)\]", text))
    for dims in (tuple(int(n) for n in s.split(",")) for s in shapes):
        assert not any(a == b == seq for a, b in zip(dims, dims[1:])), dims
        # 16,384 stands only beside the hidden size (the tied table, its
        # gradient and moments) or as a loss chunk's logits
        if 16384 in dims:
            assert dims in {(16384, 2048), (16384, 2048, 1),
                            (4, 1024, 16384)}, dims
    others = {model[k] for k in ("hidden_size", "intermediate_size",
                                 "moe_intermediate_size",
                                 "num_experts_published")}
    others |= {seq, batch * seq, batch * seq // 8,
               batch * seq * model["num_experts_per_tok"], 3 * 2048,
               2 * 1792, 32 * 64, 8 * 64}
    assert model["vocab_size"] == 16384 and 16384 not in others
    print(f"planned {planned / 2**30:.3f} GiB", compiled.memory_analysis())


def test_delta_rule_expert_step_fits_one_chip_at_two_8k_sequences(
        topo, no_compile_cache, on_tpu):
    """The cut configuration of the cell ``qwen3-next-80b-a3b.step-8k``
    (published layers 0 to 3 at the published widths: three gated
    delta-rule layers and one gated-attention layer, each with 32 of 512
    softmax-routed experts beside a gated shared expert, an eighth of the
    vocabulary under an untied head), its step at 2 x 8,192 with
    recomputation, as the benchmark's family builds it: the plan stays under
    the 14.5 GiB that ISSUE 56 set for choosing the batch (13.32 read; at 4
    x 8,192 it is 17.74, which is why the cell runs the accepted ``step-8k``
    traffic) with the state's 7.0 GiB as arguments. Every linear layer's
    rule is the Pallas kernel pair over [2, 8192, 2048] keys and [2, 8192,
    4096] values, one forward and one backward call a layer (the recomputed
    block keeps the forward's output and boundary states by
    ``ops.remat.remat_policy``); every linear layer's four-tap
    convolution is the pair ``causal_conv_fwd`` / ``causal_conv_bwd`` over
    [2, 8192, 8192] (PR 57: forward, forward again in the recomputed block,
    backward; the plan did not move); attention is one flash call forward and
    one backward at 16 query heads on 2 key-value heads of 256. Each traced
    call wrote its record into the runtime's ring. No array is shaped like
    a [T, T] score matrix (the convolution's [2, T, 8192 channels] apart),
    and the vocabulary's 18,992 rows equal no other
    dimension of the program."""
    from ray_tpu._private import steptrace

    worker, model, traffic = _cut_cell("qwen3-next-80b-a3b.step-8k")
    built = worker.load_family(ROOT, model).build(model, traffic, None)
    one = SingleDeviceSharding(topo.devices[0])
    params, opt_state = _with_sharding(
        jax.eval_shape(built.make_state, jax.random.PRNGKey(0)), one)
    batch, seq = traffic["batch"], traffic["seq"]
    assert (batch, seq) == (2, 8192)
    ids = jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=one)
    steptrace.set_enabled(True)
    steptrace.reset()
    try:
        lowered = _lower_held(
            "qwen3-next-80b-a3b.step-8k", built.step, params, opt_state,
            {"input_ids": ids, "labels": ids})
        counters = [e for e in steptrace.chrome_trace(
            steptrace.merge_records(steptrace.snapshot())) if e["ph"] == "C"]
    finally:
        steptrace.set_enabled(False)
    by_name = collections.defaultdict(list)
    for e in counters:
        by_name[e["name"]].append(e["args"])
    assert set(by_name) == {"attn/grid_blocks", "delta/rule", "conv/causal",
                            "model/layer_kinds", "attention/boundary",
                            "moe/row_buffers", "moe/to_tokens",
                            "moe/grouped_matmul"}
    assert by_name["model/layer_kinds"][-1] == {
        "linear_attention": 3, "full_attention": 1, "expert": 4, "layers": 4,
        "published_layers": 48}
    assert {(e["heads"], e["kv_heads"], e["d_qk"], e["d_v"],
             e["model_results"]) for e in by_name["attention/boundary"]} == {
        (16, 2, 256, 256, 1)}
    assert {e["backward"] for e in by_name["delta/rule"]} == {0, 1}
    tokens = batch * seq
    for e in by_name["delta/rule"]:
        assert e == {"heads": 32, "key_heads": 16, "d_k": 128, "d_v": 128,
                     "tokens": tokens, "sequences": batch, "chunk": 64,
                     "boundary_bytes": tokens * 4096 * 2,   # the output's
                     "bytes_needed": tokens * (41_472 if e["backward"]
                                               else 24_832),
                     "backward": e["backward"],
                     # a grid step is two key heads' groups: two value
                     # heads each x four chunks (PR 59)
                     "problems_a_step": 16,
                     "grid_steps": batch * 8 * seq // 256}
    assert {e["backward"] for e in by_name["conv/causal"]} == {0, 1}
    cells = tokens * 8192 * 2
    for e in by_name["conv/causal"]:
        assert e == {"channels": 8192, "taps": 4, "tokens": tokens,
                     "sequences": batch, "activation": 1, "bias": 0,
                     "backward": e["backward"],
                     "bytes_needed": (3 * cells + 2 * 4 * 8192 * 4
                                      if e["backward"]
                                      else 2 * cells + 4 * 8192 * 4)}
    compiled = lowered.compile()
    planned = _device_bytes(compiled)
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    assert n_params == 625_667_136 + 4 * 512
    assert 3 * 4 * n_params < planned < 14.5 * 2**30
    text = compiled.as_text()
    calls = collections.Counter(re.findall(
        r"^\s*%?((?:flash|gated_delta|causal_conv)_(?:fwd|bwd)(?:_w\d+)?)"
        r'[\w.\-]* = .*custom_call_target="tpu_custom_call"', text, re.M))
    # every linear layer's convolution is the kernel pair (PR 57): forward,
    # forward again in the recomputed block, backward
    assert calls == {"flash_fwd": 1, "flash_bwd": 1, "gated_delta_fwd": 3,
                     "gated_delta_bwd": 3, "causal_conv_fwd": 6,
                     "causal_conv_bwd": 3}
    assert "bf16[32,8192,256]" in text and "bf16[4,8192,256]" in text
    assert "f32[2,32,32,128,128]" in text     # a boundary every 256 positions
    _dq_census(text, 32, 256, seq)
    # the four expert layers' rows go back to the tokens through the kernel
    # ``to_tokens``, forward and backward
    _to_tokens_census(text, counters, tokens, model["num_experts_per_tok"],
                      model["hidden_size"], model["num_experts"], calls=2 * 4)
    _grouped_matmul_census(
        text, counters, tokens * model["num_experts_per_tok"],
        model["hidden_size"], 2 * model["moe_intermediate_size"],
        model["moe_intermediate_size"], model["num_experts"], layers=4)
    shapes = set(re.findall(r"\b[a-z]\w*\[([\d,]+)\]", text))
    # the convolution's 8,192 channels (q, k and v: 2 x 2048 + 4096) happen
    # to equal the length: [batch, T, channels] is no score matrix, which
    # would carry 16 heads or 2 x 16 before its [T, T]
    channels = (batch, seq, 2 * 2048 + 4096)
    for dims in (tuple(int(n) for n in s.split(",")) for s in shapes):
        assert dims == channels or not any(
            a == b == seq for a, b in zip(dims, dims[1:])), dims
        # 18,992 stands only beside the hidden size (the embedding, the
        # head, their gradients and moments) or as a loss chunk's logits
        if 18992 in dims:
            assert dims in {(18992, 2048), (18992, 2048, 1),
                            (2, 1024, 18992)}, dims
    print(f"planned {planned / 2**30:.3f} GiB", compiled.memory_analysis())


def test_mamba2_relu2_expert_step_fits_one_chip_at_two_8k_sequences(
        topo, no_compile_cache, on_tpu):
    """The cut configuration of the cell ``nemotron-3-nano-30b-a3b.step-8k``
    (published blocks 0 to 8, ``MEMEM*EME``, at the published widths: four
    Mamba-2 mixers, four blocks of 8 of 128 sigmoid-routed un-gated relu^2
    experts beside a shared expert, one attention block at 32 query heads on
    2 key-value heads of 128, an eighth of the vocabulary under an untied
    head), its step at 2 x 8,192 with recomputation, as the benchmark's
    family builds it: the plan stays under the 14.5 GiB that ISSUE 58 set for
    choosing the batch, with the state's 8.0 GB as arguments. Every Mamba
    block's scan is the Pallas pair ``ssd_fwd`` / ``ssd_bwd`` over the
    model's own [2, 8192, 4096] and [2, 8192, 1024] arrays, one forward and
    one backward call a block (the recomputed block keeps the forward's
    output and boundary states by ``ops.remat.remat_policy``); its
    convolutions (x, B and C each on its own, with the bias) are the pair
    ``causal_conv_fwd`` / ``causal_conv_bwd``: forward, forward again in the
    recomputed block, backward; the gated, grouped norm between the scan and
    the out-projection is the pair ``group_norm_fwd`` / ``group_norm_bwd``
    in the same three passes (``_group_norm_census``, PR 64); attention is
    one flash call each way. Each
    traced call wrote its record into the runtime's ring. No array is shaped
    like a [T, T] score matrix, none like a state a position ([.., T, heads,
    64, 128])."""
    from ray_tpu._private import steptrace

    cell = "nemotron-3-nano-30b-a3b.step-8k"
    worker, model, traffic = _cut_cell(cell)
    built = worker.load_family(ROOT, model).build(model, traffic, None)
    one = SingleDeviceSharding(topo.devices[0])
    params, opt_state = _with_sharding(
        jax.eval_shape(built.make_state, jax.random.PRNGKey(0)), one)
    batch, seq = traffic["batch"], traffic["seq"]
    assert (batch, seq) == (2, 8192)
    ids = jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=one)
    steptrace.set_enabled(True)
    steptrace.reset()
    try:
        lowered = _lower_held(cell, built.step, params, opt_state,
                              {"input_ids": ids, "labels": ids})
        counters = [e for e in steptrace.chrome_trace(
            steptrace.merge_records(steptrace.snapshot())) if e["ph"] == "C"]
    finally:
        steptrace.set_enabled(False)
    by_name = collections.defaultdict(list)
    for e in counters:
        by_name[e["name"]].append(e["args"])
    assert set(by_name) == {"attn/grid_blocks", "ssd/scan", "conv/causal",
                            "model/layer_kinds", "attention/boundary",
                            "moe/row_buffers", "moe/to_tokens",
                            "moe/grouped_matmul", "norm/gated_group"}
    assert by_name["model/layer_kinds"][-1] == {
        "mamba": 4, "attention": 1, "expert": 4, "layers": 9,
        "published_layers": 52}
    assert {(e["heads"], e["kv_heads"], e["d_qk"], e["d_v"],
             e["model_results"]) for e in by_name["attention/boundary"]} == {
        (32, 2, 128, 128, 1)}
    tokens = batch * seq
    assert {e["backward"] for e in by_name["ssd/scan"]} == {0, 1}
    for e in by_name["ssd/scan"]:
        assert e == {"heads": 64, "groups": 8, "head_dim": 64, "states": 128,
                     "tokens": tokens, "sequences": batch, "chunk": 128,
                     "stride": 256,
                     "boundary_bytes": tokens * 4096 * 2,   # the output's
                     "bytes_needed": tokens * (33_280 if e["backward"]
                                               else 20_736),
                     "backward": e["backward"]}
    assert {(e["channels"], e["taps"], e["bias"], e["activation"])
            for e in by_name["conv/causal"]} == {(4096, 4, 1, 1),
                                                 (1024, 4, 1, 1)}
    compiled = lowered.compile()
    planned = _device_bytes(compiled)
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    assert n_params == 666_963_456          # ISSUE 58's count
    assert 3 * 4 * n_params < planned < 14.5 * 2**30
    text = compiled.as_text()
    calls = collections.Counter(re.findall(
        r"^\s*%?((?:flash|ssd|causal_conv|group_norm)_(?:fwd|bwd)(?:_w\d+)?)"
        r'[\w.\-]* = .*custom_call_target="tpu_custom_call"', text, re.M))
    # three convolutions and one norm a Mamba block: forward, forward again
    # in the recomputed block, backward; the scan's forward is NOT run again
    assert calls == {"flash_fwd": 1, "flash_bwd": 1, "ssd_fwd": 4,
                     "ssd_bwd": 4, "causal_conv_fwd": 24,
                     "causal_conv_bwd": 12, "group_norm_fwd": 8,
                     "group_norm_bwd": 4}
    assert "f32[2,32,32,128,128]" in text     # a boundary every 256 positions
    _dq_census(text, 64, 128, seq)
    _group_norm_census(text, counters, blocks=4, batch=batch, seq=seq,
                       width=4096, groups=8)
    _to_tokens_census(text, counters, tokens, model["num_experts_per_tok"],
                      model["hidden_size"], model["n_routed_experts"],
                      calls=2 * 4, block=256)
    # un-gated experts: the way up is one width wide
    _grouped_matmul_census(
        text, counters, tokens * model["num_experts_per_tok"],
        model["hidden_size"], model["moe_intermediate_size"],
        model["moe_intermediate_size"], model["n_routed_experts"], layers=4)
    shapes = set(re.findall(r"\b[a-z]\w*\[([\d,]+)\]", text))
    for dims in (tuple(int(n) for n in s.split(",")) for s in shapes):
        assert not any(a == b == seq for a, b in zip(dims, dims[1:])), dims
        # no state a position: [.., 64, 128] stands only in the kept
        # boundaries, 32 a sequence
        if dims[-2:] == (64, 128) or dims[-2:] == (128, 128):
            assert seq not in dims and tokens not in dims, dims
    print(f"planned {planned / 2**30:.3f} GiB", compiled.memory_analysis())


def test_window_over_full_rotary_expert_step_fits_one_chip_at_two_8k_sequences(
        topo, no_compile_cache, on_tpu):
    """The cut configuration of the cell ``mellum2-12b-a2.5b.step-8k``
    (published layers 0 to 3 at the published widths: three window layers of
    1,024 keys and one YaRN-scaled full layer, 32 query heads on 4 of 128,
    every feed-forward 16 of 64 softmax-routed experts of 896, a quarter of
    the vocabulary under an untied head), its step at 2 x 8,192 with
    recomputation, as the benchmark's family builds it: the plan stays under
    the 14.5 GiB that ISSUE 62 set for choosing the batch (11.88 read; at 4
    x 8,192 it is 16.59, over the chip, which is why the cell runs the
    accepted ``step-8k`` traffic) with the state's 6.65 GiB as arguments.
    Every layer's attention is the flash kernel pair on the
    ``model_results`` boundary, once forward and once backward
    (``ops.remat.remat_policy`` keeps the output and its log-sum-exp), the
    window layers' named ``flash_*_w1024``; under the window a grid step
    holds the window's own 1,024 queries and keys (``_block_sizes``, PR 62),
    so a head is 8 x 8 blocks told apart by their place (8 diagonal, 7
    trailing, 49 dead, none looped), of which the windowed calls' grids
    launch a row's trailing and diagonal block alone (PR 66: 16 steps a
    head, 1 dead; ``_windowed_calls_census``), the full layer's 4 x 4 of
    2,048. Two
    rotary tables are built a traced pass, one plain and one YaRN. The four
    expert layers' matmuls are the grouped-matmul kernels at 2,304 x 1,792
    / 896 over 16 groups. No array is shaped like a [T, T] score matrix, and
    the vocabulary's 24,576 rows equal no other dimension of the program."""
    from ray_tpu._private import steptrace

    worker, model, traffic = _cut_cell("mellum2-12b-a2.5b.step-8k")
    built = worker.load_family(ROOT, model).build(model, traffic, None)
    one = SingleDeviceSharding(topo.devices[0])
    params, opt_state = _with_sharding(
        jax.eval_shape(built.make_state, jax.random.PRNGKey(0)), one)
    batch, seq = traffic["batch"], traffic["seq"]
    assert (batch, seq) == (2, 8192)
    ids = jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=one)
    steptrace.set_enabled(True)
    steptrace.reset()
    try:
        lowered = _lower_held(
            "mellum2-12b-a2.5b.step-8k", built.step, params, opt_state,
            {"input_ids": ids, "labels": ids})
        counters = [e for e in steptrace.chrome_trace(
            steptrace.merge_records(steptrace.snapshot())) if e["ph"] == "C"]
    finally:
        steptrace.set_enabled(False)
    by_name = collections.defaultdict(list)
    for e in counters:
        by_name[e["name"]].append(e["args"])
    assert set(by_name) == {"attn/grid_blocks", "rope/table",
                            "model/layer_kinds", "attention/boundary",
                            "attention/head_rotary", "moe/row_buffers",
                            "moe/to_tokens", "moe/grouped_matmul"}
    assert by_name["model/layer_kinds"][-1] == {
        "sliding_attention": 3, "full_attention": 1, "expert": 4,
        "layers": 4, "published_layers": 28}
    tables = {e["kind"]: e for e in by_name["rope/table"]}
    assert tables["plain"] == {
        "kind": "plain", "theta": 500000.0, "factor": 1.0, "original": 0,
        "low": 0, "high": 0, "attention_factor": 1.0, "dims": 128}
    assert tables["yarn"] == {
        "kind": "yarn", "theta": 500000.0, "factor": 16.0, "original": 8192,
        "low": 18, "high": 35, "attention_factor": 1.2772588722239782,
        "dims": 128}
    assert {(e["heads"], e["kv_heads"], e["d_qk"], e["d_v"],
             e["model_results"]) for e in by_name["attention/boundary"]} == {
        (32, 4, 128, 128, 1)}
    # the windowed calls' grids hold a row's trailing and diagonal block
    # alone, 16 steps a head, one of them past the head's edge (PR 66);
    # the full layer's every block
    by_window = {1024: (0, 8, 7, 49, 16, 1), 0: (6, 4, 0, 6, 16, 6)}
    assert {(e["window"], e["backward"])
            for e in by_name["attn/grid_blocks"]} == {
        (w, b) for w in by_window for b in (0, 1)}
    for e in by_name["attn/grid_blocks"]:
        whole, diagonal, trailing, dead, steps, dead_steps = by_window[
            e["window"]]
        assert e == {
            "whole": whole, "diagonal": diagonal, "trailing": trailing,
            "dead": dead, "looped": 0, "queries": seq, "keys": seq,
            "backward": e["backward"], "window": e["window"],
            "heads": batch * 32, "kv_heads": batch * 4, "dq_partials": 0,
            "steps": steps, "dead_steps": dead_steps}
    compiled = lowered.compile()
    planned = _device_bytes(compiled)
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    assert n_params == 595_154_432
    assert 3 * 4 * n_params < planned < 14.5 * 2**30
    assert planned > 4 * 2**30     # a quarter of the chip's 16 and more
    text = compiled.as_text()
    calls = collections.Counter(re.findall(
        r"^\s*%?(flash_(?:fwd|bwd)(?:_w\d+)?)[\w.\-]* = .*"
        r'custom_call_target="tpu_custom_call"', text, re.M))
    assert calls == {"flash_fwd": 1, "flash_bwd": 1, "flash_fwd_w1024": 3,
                     "flash_bwd_w1024": 3}
    assert "bf16[64,8192,128]" in text and "bf16[8,8192,128]" in text
    _windowed_calls_census(text, 64, 8, seq, 1024, layers=3)
    _dq_census(text, 64, 128, seq)
    _head_rotary_census(text, counters, batch * seq * 32 * 128, layers=4,
                        rotated=4)
    tokens = batch * seq
    # blocks of 256 tokens: at 2,304 wide a block of 512 and its chunks'
    # slots do not fit the kernel's VMEM (``ops.moe._token_blocks``)
    _to_tokens_census(text, counters, tokens, model["num_experts_per_tok"],
                      model["hidden_size"], model["num_experts"], calls=2 * 4,
                      block=256)
    _grouped_matmul_census(
        text, counters, tokens * model["num_experts_per_tok"],
        model["hidden_size"], 2 * model["moe_intermediate_size"],
        model["moe_intermediate_size"], model["num_experts"], layers=4)
    shapes = set(re.findall(r"\b[a-z]\w*\[([\d,]+)\]", text))
    for dims in (tuple(int(n) for n in s.split(",")) for s in shapes):
        assert not any(a == b == seq for a, b in zip(dims, dims[1:])), dims
        # 24,576 stands only beside the hidden size (the embedding, the
        # head, their gradients and moments) or as a loss chunk's logits
        if 24576 in dims:
            assert dims in {(24576, 2304), (24576, 2304, 1),
                            (2, 1024, 24576)}, dims
    print(f"planned {planned / 2**30:.3f} GiB", compiled.memory_analysis())


def test_block_diffusion_expert_step_fits_one_chip_at_two_4k_sequences(
        topo, no_compile_cache, on_tpu):
    """The cut configuration of the cell ``sdar-30b-a3b-chat.step-bd-4k``
    (published layers 0 to 5 at the published widths: 32 query heads on 4
    of 128 under the block-diffusion mask, every feed-forward 16 of 128
    softmax-routed experts of 768, an eighth of the vocabulary under an
    untied head), its step at 2 x 4,096 DATA tokens with recomputation, as
    the benchmark's family builds it: the program runs a noisy and a clean
    copy of each sequence, 2 x 8,192 positions through the trunk, and the
    head over the noisy 2 x 4,096 alone. The plan stays under the 14.5 GiB
    that ISSUE 65 set for keeping six layers (12.22 read: 7.22 of arguments,
    5.00 of temporaries). Every layer's attention is the flash kernel pair
    under the mask, named ``flash_*_bd4``, on the ``model_results``
    boundary, once forward and once backward (``ops.remat.remat_policy``
    keeps the output and its log-sum-exp); a head's grid is 4 x 4 blocks of
    2,048, two residents a stream, of which the kernels walk 8 (2 whole, 2
    of each kind an edge crosses) and skip 8. PR 63's prologue writes the
    kernels' operands under a table of repeated positions. No array is
    shaped like a [2L, 2L] score matrix, and the vocabulary's 18,992 rows
    stand only beside the hidden size and the loss walk's positions."""
    from ray_tpu._private import steptrace

    worker, model, traffic = _cut_cell("sdar-30b-a3b-chat.step-bd-4k")
    built = worker.load_family(ROOT, model).build(model, traffic, None)
    one = SingleDeviceSharding(topo.devices[0])
    params, opt_state = _with_sharding(
        jax.eval_shape(built.make_state, jax.random.PRNGKey(0)), one)
    batch, seq = traffic["batch"], traffic["seq"]
    assert (batch, seq) == (2, 4096)
    positions = 2 * seq           # a sequence's two streams
    ids = jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=one)
    steptrace.set_enabled(True)
    steptrace.reset()
    try:
        lowered = _lower_held(
            "sdar-30b-a3b-chat.step-bd-4k", built.step, params, opt_state,
            {"input_ids": ids, "labels": ids})
        counters = [e for e in steptrace.chrome_trace(
            steptrace.merge_records(steptrace.snapshot())) if e["ph"] == "C"]
    finally:
        steptrace.set_enabled(False)
    by_name = collections.defaultdict(list)
    for e in counters:
        by_name[e["name"]].append(e["args"])
    assert set(by_name) == {"attn/grid_blocks", "rope/table",
                            "model/layer_kinds", "attention/boundary",
                            "attention/head_rotary", "moe/row_buffers",
                            "moe/to_tokens", "moe/grouped_matmul"}
    assert by_name["model/layer_kinds"][-1] == {
        "block_diffusion": 6, "expert": 6, "layers": 6,
        "published_layers": 48, "block_length": 4, "streams": 2}
    assert {tuple(sorted(e.items()))
            for e in by_name["attention/boundary"]} == {tuple(sorted({
        "tokens": positions, "heads": 32, "kv_heads": 4, "d_qk": 128,
        "d_v": 128, "window": 0, "heads_a_lane_tile": 0, "model_arrays": 0,
        "model_results": 1, "blocks": 4, "kernel": 1,
        "live_blocks": batch * 32 * 8,
        "skipped_blocks": batch * 32 * 8}.items()))}
    for e in by_name["attn/grid_blocks"]:
        assert e == {
            "whole": 2, "own": 2, "strict": 2, "inclusive": 2, "dead": 8,
            "queries": positions, "keys": positions,
            "backward": e["backward"], "window": 0, "blocks": 4,
            "heads": batch * 32, "kv_heads": batch * 4, "dq_partials": 0,
            "steps": 16, "dead_steps": 8}
    compiled = lowered.compile()
    planned = _device_bytes(compiled)
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    assert n_params == 645_624_064
    assert 3 * 4 * n_params < planned < 14.5 * 2**30
    assert planned > 4 * 2**30     # a quarter of the chip's 16 and more
    print(f"planned {planned / 2**30:.2f} GiB")
    text = compiled.as_text()
    calls = collections.Counter(re.findall(
        r"^\s*%?(flash_(?:fwd|bwd)(?:_(?:w|bd)\d+)?)[\w.\-]* = .*"
        r'custom_call_target="tpu_custom_call"', text, re.M))
    assert calls == {"flash_fwd_bd4": 6, "flash_bwd_bd4": 6}
    assert f"bf16[64,{positions},128]" in text
    assert f"bf16[8,{positions},128]" in text
    _dq_census(text, 64, 128, positions)
    _head_rotary_census(text, counters, batch * positions * 32 * 128,
                        layers=6, rotated=6)
    tokens = batch * positions
    _to_tokens_census(text, counters, tokens, model["num_experts_per_tok"],
                      model["hidden_size"], model["num_experts"], calls=2 * 6)
    _grouped_matmul_census(
        text, counters, tokens * model["num_experts_per_tok"],
        model["hidden_size"], 2 * model["moe_intermediate_size"],
        model["moe_intermediate_size"], model["num_experts"], layers=6)
    shapes = set(re.findall(r"\b[a-z]\w*\[([\d,]+)\]", text))
    for dims in (tuple(int(n) for n in s.split(",")) for s in shapes):
        # no [2L, 2L] (nor [L, L]) a head: no mask, score or probability
        assert not any(a == b and a in (positions, seq)
                       for a, b in zip(dims, dims[1:])), dims
        if 18992 in dims:
            assert set(dims) <= {18992, 2048, batch, seq // 8, 1}, dims


def test_selected_attention_expert_step_fits_one_chip_at_16384(
        topo, no_compile_cache, on_tpu):
    """The cut configuration of the cell ``keye-vl-2.0-30b-a3b.step-16k-img``
    (published layers 0 to 5 at the published widths: 32 query heads on 4
    of 128, in front of every layer an indexer of 16 index heads of 64 on
    one index key that picks 2,048 keys a query, every feed-forward 16 of
    128 softmax-routed experts of 768, an eighth of the vocabulary under an
    untied head), its step at one sequence of 16,384 with recomputation, as
    the benchmark's family builds it over a batch that carries the
    layout's position ids and loss weights. The plan stays under the 14.5
    GiB that ISSUE 67 set for keeping six layers (14.37 read at PR 68, 14.18
    at PR 67: 7.37 of arguments, the rest temporaries, the six layers' kept
    words 0.19 of them). Every layer's attention is the flash
    kernel pair under the selection, named ``flash_*_sel2048``, once
    forward and once backward (``ops.remat.remat_policy`` keeps the output
    and its log-sum-exp); the selection's kernel runs ONCE a layer too
    (since PR 68 the mask is bits, int32 [1, T / 32, T], 32 MiB a layer,
    which the policy keeps: the recomputed block's flash backward reads the
    kept words), and so does the KL's (its forward rule's gradients are
    kept). PR 63's prologue writes the flash kernels' operands under a table
    whose pairs read three position rows. No [T, T] array is left, a byte or
    a score a pair: what crosses the kernels' boundary of the selection is
    its words; the vocabulary's 18,992 rows stand only beside the hidden size
    and the loss walk's positions."""
    from ray_tpu._private import steptrace

    cell = "keye-vl-2.0-30b-a3b.step-16k-img"
    worker, model, traffic = _cut_cell(cell)
    built = worker.load_family(ROOT, model).build(model, traffic, None)
    one = SingleDeviceSharding(topo.devices[0])
    params, opt_state = _with_sharding(
        jax.eval_shape(built.make_state, jax.random.PRNGKey(0)), one)
    batch, seq = traffic["batch"], traffic["seq"]
    assert (batch, seq) == (1, 16384)
    ids = jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=one)
    laid = _with_sharding(jax.eval_shape(lambda: built.extra), one)
    steptrace.set_enabled(True)
    steptrace.reset()
    try:
        lowered = _lower_held(cell, built.step, params, opt_state,
                              {"input_ids": ids, "labels": ids, **laid})
        counters = [e for e in steptrace.chrome_trace(
            steptrace.merge_records(steptrace.snapshot())) if e["ph"] == "C"]
    finally:
        steptrace.set_enabled(False)
    by_name = collections.defaultdict(list)
    for e in counters:
        by_name[e["name"]].append(e["args"])
    assert set(by_name) == {
        "attn/grid_blocks", "rope/table", "model/layer_kinds",
        "attention/boundary", "attention/head_rotary", "attn/selected",
        "index/scores", "index/threshold", "index/kept", "index/loss",
        "moe/row_buffers",
        "moe/to_tokens", "moe/grouped_matmul"}
    assert by_name["model/layer_kinds"][-1] == {
        "sparse": 6, "expert": 6, "layers": 6, "published_layers": 48,
        "topk": 2048}
    assert by_name["rope/table"][-1]["kind"] == "sections"
    causal = seq * (seq + 1) // 2
    assert by_name["attn/selected"][-1] == {
        "topk": 2048, "rows": seq, "heads": 32,
        "pairs_selected": 31_458_304, "pairs_causal": causal,
        "dead_tiles": 0, "kernel": 1}
    assert by_name["index/scores"][-1] == {
        "heads": 16, "width": 64, "rows": seq, "pairs": causal, "topk": 2048,
        "kernel": 1, "flops_needed": 2 * causal * 16 * 64,
        "bytes_needed": seq * (17 * 64 * 2 + 64), "operand_bits": 16}
    assert by_name["index/threshold"][-1]["passes"] == 32
    assert by_name["index/kept"][-1] == {
        "bits_a_pair": 1, "bytes": seq * seq // 8, "kernel": 1}
    assert by_name["index/loss"][-1]["kernel"] == 1
    for e in by_name["attn/grid_blocks"]:
        assert (e["whole"], e["diagonal"], e["dead"]) == (28, 8, 28)
    compiled = lowered.compile()
    planned = _device_bytes(compiled)
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    assert n_params == 659_190_784
    assert 3 * 4 * n_params < planned < 14.5 * 2**30
    assert planned > 4 * 2**30     # a quarter of the chip's 16 and more
    print(f"planned {planned / 2**30:.2f} GiB")
    text = compiled.as_text()
    calls = collections.Counter(re.findall(
        r"^\s*%?(flash_(?:fwd|bwd)(?:_(?:w|bd|sel)\d+)?|index_select_top\d+"
        r"|index_kl)[\w.\-]* = .*"
        r'custom_call_target="tpu_custom_call"', text, re.M))
    assert calls == {"flash_fwd_sel2048": 6, "flash_bwd_sel2048": 6,
                     "index_select_top2048": 6, "index_kl": 6}
    assert f"bf16[32,{seq},128]" in text and f"bf16[4,{seq},128]" in text
    assert f"s32[1,{seq // 32},{seq}]" in text
    _dq_census(text, 32, 128, seq)
    _head_rotary_census(text, counters, batch * seq * 32 * 128, layers=6,
                        rotated=6)
    _to_tokens_census(text, counters, seq, model["num_experts_per_tok"],
                      model["hidden_size"], model["num_experts"], calls=2 * 6)
    _grouped_matmul_census(
        text, counters, seq * model["num_experts_per_tok"],
        model["hidden_size"], 2 * model["moe_intermediate_size"],
        model["moe_intermediate_size"], model["num_experts"], layers=6)
    shapes = set(re.findall(r"\b([a-z]\w*)\[([\d,]+)\]", text))
    for kind, dims in ((k, tuple(int(n) for n in s.split(",")))
                       for k, s in shapes):
        # no [T, T]: no mask a byte a pair, no score, no probability
        assert not any(a == b == seq for a, b in zip(dims, dims[1:])), (
            kind, dims)
        if 18992 in dims:
            assert set(dims) <= {18992, 2048, batch, seq // 8, 1}, dims

