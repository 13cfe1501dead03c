"""Model-family tests: Llama (train/decode/TP), ViT, ResNet.

Parity model: the reference trains/serves these families through torch
integrations (ray: release/air_tests/air_benchmarks/workloads/,
python/ray/serve release LLM tests); here they are native flax modules."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama, vision


def test_llama_train_step_loss_decreases():
    cfg = llama.LlamaConfig.small_test()
    model, params = llama.init_params(cfg, jax.random.PRNGKey(0))
    import optax

    tx = optax.adamw(1e-2)
    opt_state = tx.init(params)
    step = llama.build_train_step(model, tx, donate=False)
    batch = llama.synthetic_batch(jax.random.PRNGKey(1), 4, 32, cfg.vocab_size)
    first = None
    for _ in range(10):
        params, opt_state, loss = step(params, opt_state, batch)
        if first is None:
            first = float(loss)
    assert float(loss) < first


def test_llama_decode_matches_full_pass():
    """KV-cache decode must produce the same logits as the full causal
    pass — the correctness contract for the serving path."""
    cfg = llama.LlamaConfig.small_test()
    model, params = llama.init_params(cfg, jax.random.PRNGKey(0))
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0,
                             cfg.vocab_size, dtype=jnp.int32)
    full_logits, _ = model.apply({"params": params}, ids)

    caches = llama.init_kv_caches(cfg, 2, max_len=16)
    decode = llama.build_decode_step(model)
    for t in range(ids.shape[1]):
        logits, caches = decode(params, ids[:, t:t + 1], jnp.int32(t), caches)
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(full_logits[:, -1, :]),
        rtol=0.05, atol=0.05,  # bf16 compute
    )


def test_llama_generate_greedy():
    cfg = llama.LlamaConfig.small_test()
    model, params = llama.init_params(cfg, jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(2), (2, 4), 0,
                                cfg.vocab_size, dtype=jnp.int32)
    out = llama.generate(model, params, prompt, max_new_tokens=6)
    assert out.shape == (2, 10)
    assert (np.asarray(out[:, :4]) == np.asarray(prompt)).all()
    # prefill correctness: the first generated token must equal the argmax
    # of the FULL causal pass over the prompt (regression: the cache-branch
    # mask once let prefill queries attend only to position 0)
    full_logits, _ = model.apply({"params": params}, prompt)
    expect = np.asarray(jnp.argmax(full_logits[:, -1, :], axis=-1))
    assert (np.asarray(out[:, 4]) == expect).all()
    # temperature>0 without an rng is a usage error, not a crash deep in jax
    with pytest.raises(ValueError):
        llama.generate(model, params, prompt, 2, temperature=0.5)


def test_llama_gqa_heads():
    """n_kv_head < n_head (grouped-query) must broadcast correctly."""
    cfg = llama.LlamaConfig.small_test(n_head=4, n_kv_head=1)
    model, params = llama.init_params(cfg, jax.random.PRNGKey(0))
    ids = jnp.zeros((1, 8), dtype=jnp.int32)
    logits, _ = model.apply({"params": params}, ids)
    assert logits.shape == (1, 8, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits, dtype=np.float32)).all()


def test_llama_tp_sharding_specs():
    from ray_tpu.parallel.mesh_utils import create_mesh

    mesh = create_mesh({"model": 2})
    cfg = llama.LlamaConfig.small_test()
    model, params = llama.init_params(cfg, jax.random.PRNGKey(0))
    shardings = llama.shard_params_tp(params, mesh)
    qspec = shardings["h_0"]["attn"]["q_proj"]["kernel"].spec
    ospec = shardings["h_0"]["attn"]["o_proj"]["kernel"].spec
    assert qspec == jax.sharding.PartitionSpec(None, "model")
    assert ospec == jax.sharding.PartitionSpec("model", None)
    # placed forward pass still agrees with the unsharded one
    placed = jax.tree.map(jax.device_put, params, shardings)
    ids = jnp.zeros((1, 8), dtype=jnp.int32)
    a, _ = model.apply({"params": params}, ids)
    b, _ = model.apply({"params": placed}, ids)
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), rtol=2e-2,
                               atol=2e-2)


def test_vit_forward_and_train():
    cfg = vision.ViTConfig.small_test()
    model = vision.ViT(cfg)
    params, tx, opt_state = vision.make_train_state(
        model, cfg, jax.random.PRNGKey(0), learning_rate=1e-2
    )
    step = vision.build_train_step(model, tx, donate=False)
    batch = vision.synthetic_image_batch(jax.random.PRNGKey(1), 8,
                                         cfg.image_size, cfg.num_classes)
    first = None
    for _ in range(8):
        params, opt_state, loss = step(params, opt_state, batch)
        if first is None:
            first = float(loss)
    assert float(loss) < first


def test_resnet_forward_and_train():
    cfg = vision.ResNetConfig.small_test()
    model = vision.ResNet(cfg)
    params, tx, opt_state = vision.make_train_state(
        model, cfg, jax.random.PRNGKey(0), learning_rate=1e-2
    )
    step = vision.build_train_step(model, tx, donate=False)
    batch = vision.synthetic_image_batch(jax.random.PRNGKey(1), 8, 32,
                                         cfg.num_classes)
    first = None
    for _ in range(8):
        params, opt_state, loss = step(params, opt_state, batch)
        if first is None:
            first = float(loss)
    assert float(loss) < first


def test_resnet50_config_shapes():
    cfg = vision.ResNetConfig.resnet50_cifar()
    assert cfg.stage_sizes == (3, 4, 6, 3)
    assert cfg.num_classes == 10


@pytest.mark.parametrize("dtype,value_tol,dh_tol,de_tol", [
    (jnp.float32, 1e-5, 1e-6, 1e-6),
    # bf16: the label's logit is the unrounded row dot, the fused loss
    # picks it out of logits rounded to 8 bits of mantissa
    (jnp.bfloat16, 5e-3, 1e-3, 3e-3),
], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("masked", [False, True], ids=["unmasked", "masked"])
def test_gpt2_chunked_loss_matches_fused(masked, dtype, value_tol, dh_tol,
                                         de_tol):
    """The bench's default loss path (loss_chunks>0) and its own
    derivative rule must agree with the fused [B,T,V] loss and autodiff:
    value, gradient for the hidden state and for the embedding, under a
    cotangent that is not 1; and the undifferentiated call (no gradients
    formed) gives the differentiated one's value."""
    from ray_tpu.models import gpt2

    B, T, C, V = 2, 64, 32, 97
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    hidden = jax.random.normal(k[0], (B, T, C), dtype)
    embedding = 0.3 * jax.random.normal(k[1], (V, C), jnp.float32)
    labels = jax.random.randint(k[2], (B, T), 0, V)
    mask = ((jnp.arange(T)[None, :] < 48).astype(jnp.float32)
            * jnp.ones((B, 1))) if masked else None

    def fused(h, e):
        return 3 * gpt2.fused_xent(h @ e.T.astype(h.dtype), labels, mask)

    def chunked(h, e):
        return 3 * gpt2.chunked_xent(h, e, labels, mask, n_chunks=4)

    want, (dh_want, de_want) = jax.value_and_grad(fused, (0, 1))(
        hidden, embedding)
    got, (dh, de) = jax.value_and_grad(chunked, (0, 1))(hidden, embedding)
    assert abs(float(got) - float(want)) < value_tol * float(want)
    assert float(chunked(hidden, embedding)) == pytest.approx(
        float(got), rel=1e-6)
    assert dh.dtype == hidden.dtype and de.dtype == embedding.dtype
    f32 = lambda x: np.asarray(x.astype(jnp.float32))
    np.testing.assert_allclose(f32(dh), f32(dh_want), atol=dh_tol)
    np.testing.assert_allclose(f32(de), f32(de_want), atol=de_tol)
    if masked:
        # a token the mask leaves out moves nothing
        assert not f32(dh)[:, 48:].any()


def test_gpt2_chunked_loss_through_the_model_matches_fused():
    """``loss_fn`` with ``loss_chunks`` against ``loss_fn`` without: value
    and every parameter's gradient, the tied embedding's from the head and
    from the lookup summed."""
    from ray_tpu.models import gpt2

    cfg = gpt2.GPT2Config.small_test()
    cfgc = gpt2.GPT2Config.small_test(loss_chunks=4)
    model, params = gpt2.init_params(cfg, jax.random.PRNGKey(0))
    modelc = gpt2.GPT2(cfgc)
    batch = gpt2.synthetic_batch(jax.random.PRNGKey(1), 2, 64, cfg.vocab_size)
    l1, g1 = jax.value_and_grad(gpt2.loss_fn)(params, model, batch)
    l2, g2 = jax.value_and_grad(gpt2.loss_fn)(params, modelc, batch)
    assert abs(float(l1) - float(l2)) < 1e-3
    diffs = jax.tree.map(lambda a, c: float(jnp.abs(a - c).max()), g1, g2)
    assert max(jax.tree.leaves(diffs)) < 1e-2


def _vocabulary_wide(text, vocab, op):
    """Lines of lowered StableHLO text that hold ``op`` and an array
    dimension equal to ``vocab``."""
    dim = re.compile(rf"tensor<(?:\d+x)*{vocab}x")
    return [line for line in text.splitlines()
            if f"stablehlo.{op}" in line and dim.search(line)]


def test_gpt2_chunked_loss_is_three_vocabulary_matmuls_in_one_walk():
    """What shows that the head's gradient is formed beside its loss: the
    lowered ``value_and_grad`` of ``loss_fn`` holds three ``dot_general``s
    of vocabulary width (logits, dh, dE: none recomputed) and one ``while``
    (no second scan for a backward pass), and no gather out of the logits
    for the label; the undifferentiated call holds one matmul."""
    from ray_tpu.models import gpt2

    vocab = 509  # equal to no other dimension of the program
    cfg = gpt2.GPT2Config.small_test(vocab_size=vocab, loss_chunks=4,
                                     attention="xla")
    model, params = gpt2.init_params(cfg, jax.random.PRNGKey(0))
    batch = gpt2.synthetic_batch(jax.random.PRNGKey(1), 2, 64, vocab)

    def loss(params):
        return gpt2.loss_fn(params, model, batch)

    text = jax.jit(jax.value_and_grad(loss)).lower(params).as_text()
    assert len(_vocabulary_wide(text, vocab, "dot_general")) == 3
    assert text.count("stablehlo.while") == 1
    # the lookups take rows of the table: no gather along the vocabulary
    along = re.compile(rf"tensor<(?:\d+x)+{vocab}x")
    assert not [line for line in text.splitlines()
                if "stablehlo.gather" in line and along.search(line)]

    text = jax.jit(loss).lower(params).as_text()
    assert len(_vocabulary_wide(text, vocab, "dot_general")) == 1
    assert text.count("stablehlo.while") == 1


def test_flash_pallas_interpret_tiny_seq():
    """Regression for the TPU blockspec failure at trace-time shapes: the
    lane-broadcast lse layout must lower for q_len < 128 (model init traces
    with a seq-8 dummy) and for b*h not a multiple of 8."""
    from ray_tpu.ops import attention as A

    q, k, v = (
        jax.random.normal(kk, (1, 12, 8, 64), jnp.float32)
        for kk in jax.random.split(jax.random.PRNGKey(0), 3)
    )
    out = A.flash_attention(q, k, v, causal=True, impl="pallas_interpret")
    ref = A.attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("backend,seq,head_dim,path", [
    ("tpu", 1024, 64, "flash"),   # GPT-2's geometry: measured (PERF.md)
    ("tpu", 512, 64, "flash"),    # the shortest length the kernel won at
    ("tpu", 1024, 128, "flash"),  # the other head dimension measured
    ("tpu", 640, 64, "flash"),    # 128-wide tiles, a whole head a grid step
    ("tpu", 4096, 64, "flash"),   # 2048 resident: several grid blocks
    ("tpu", 2176, 64, "xla"),     # 17 x 128: 128-wide grid blocks lose
    ("tpu", 2560, 128, "xla"),    # past 2048 and 1024 does not divide it
    ("tpu", 384, 64, "xla"),      # below the crossover
    ("tpu", 8, 64, "xla"),        # the seq-8 dummy that init traces with
    ("tpu", 1000, 64, "xla"),     # no 128-multiple tile divides it
    ("tpu", 1024, 80, "xla"),     # a head dimension never measured
    ("cpu", 1024, 64, "xla"),
    ("gpu", 1024, 64, "xla"),
])
def test_auto_attention_reads_backend_and_shape(monkeypatch, backend, seq,
                                                head_dim, path):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    q = jax.ShapeDtypeStruct((2, seq, 3, head_dim), jnp.bfloat16)
    assert _auto_path(q) == path


def _auto_path(q):
    """What ``auto_attention`` answers for ``q`` as a jitted step sees it."""
    from ray_tpu.ops import attention as A

    seen = []
    jax.jit(lambda q: seen.append(A.auto_attention(q))).lower(q)
    return seen[0]


@pytest.mark.parametrize("axes,shape,path", [
    (("data",), (4,), "flash"),
    (("data", "fsdp"), (2, 2), "flash"),
    # the kernel's shard_map wrapper maps the batch axes alone: under any
    # other axis the partitioner would meet the Mosaic call and refuse it
    (("data", "model"), (2, 2), "xla"),
    (("data", "model"), (4, 1), "flash"),
    (("fsdp", "seq"), (2, 2), "xla"),
    (("data", "expert"), (2, 2), "xla"),
])
def test_auto_attention_reads_the_mesh(monkeypatch, axes, shape, path):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(shape), axes)
    q = jax.ShapeDtypeStruct(
        (4, 1024, 12, 64), jnp.bfloat16,
        sharding=NamedSharding(mesh, PartitionSpec(axes[0])))
    assert _auto_path(q) == path


@pytest.mark.parametrize("backend", ["tpu", "cpu"])
def test_auto_attention_dispatch(monkeypatch, backend):
    """``auto`` at GPT-2's geometry calls the flash kernel where the backend
    reads "tpu" and ``dot_product_attention`` elsewhere; ``xla`` never calls
    the kernel; an unknown name is refused."""
    from ray_tpu.models import gpt2
    from ray_tpu.ops import attention as A

    calls = []
    real_flash, real_xla = A.flash_attention, jax.nn.dot_product_attention
    monkeypatch.setattr(
        A, "flash_attention",
        lambda *a, **kw: calls.append("flash")
        or real_flash(*a, impl="scan", **kw))
    monkeypatch.setattr(
        jax.nn, "dot_product_attention",
        lambda *a, **kw: calls.append("xla") or real_xla(*a, **kw))
    monkeypatch.setattr(jax, "default_backend", lambda: backend)

    def paths(attention, seq):
        calls.clear()
        cfg = gpt2.GPT2Config(vocab_size=64, n_positions=seq, n_embd=128,
                              n_layer=1, n_head=2, attention=attention)
        ids = jax.ShapeDtypeStruct((1, seq), jnp.int32)
        jax.eval_shape(
            lambda i: gpt2.GPT2(cfg).init(jax.random.PRNGKey(0), i), ids)
        return set(calls)

    assert paths("auto", 1024) == {"flash" if backend == "tpu" else "xla"}
    assert paths("auto", 128) == {"xla"}
    assert paths("xla", 1024) == {"xla"}
    assert paths("flash", 1024) == {"flash"}
    with pytest.raises(ValueError, match="attention='splash'"):
        paths("splash", 128)


@pytest.mark.parametrize("n_head,n_embd", [(2, 128), (3, 192), (2, 256)],
                         ids=["two_heads_64", "odd_heads_64", "heads_128"])
def test_gpt2_on_the_kernels_own_address_is_the_xla_model(monkeypatch, n_head,
                                                          n_embd):
    """GPT-2 with ``attention="flash"`` at a length and widths whose
    kernels address the model's [B, T, H x d] arrays (interpret mode: two
    64-wide heads a lane tile, an odd head out, one 128-wide): the loss and
    every gradient are ``attention="xla"``'s."""
    import functools

    from ray_tpu.models import gpt2
    from ray_tpu.ops import attention as A

    d = n_embd // n_head
    assert A.heads_a_lane_tile(128, n_head, n_head, d, d)
    monkeypatch.setattr(A, "flash_attention", functools.partial(
        A.flash_attention, impl="pallas_interpret"))

    def loss_and_grads(attention):
        cfg = gpt2.GPT2Config(vocab_size=256, n_positions=128, n_embd=n_embd,
                              n_layer=2, n_head=n_head, attention=attention,
                              dtype=jnp.float32)
        model, params = gpt2.init_params(cfg, jax.random.PRNGKey(0))
        batch = gpt2.synthetic_batch(jax.random.PRNGKey(1), 2, 128, 256)
        return jax.value_and_grad(
            lambda p: gpt2.loss_fn(p, model, batch))(params)

    (loss, grads), (want, want_grads) = map(loss_and_grads, ("flash", "xla"))
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        a, b, atol=2e-5, rtol=2e-4), grads, want_grads)


def test_gpt2_auto_is_the_xla_program_on_cpu():
    """On the CPU backend ``auto`` lowers to the very program ``xla`` does."""
    from ray_tpu.models import gpt2

    def lowered(attention):
        cfg = gpt2.GPT2Config.small_test(attention=attention)
        model, params, tx, opt_state = gpt2.make_train_state(
            cfg, jax.random.PRNGKey(0))
        batch = gpt2.synthetic_batch(jax.random.PRNGKey(1), 2, 128,
                                     cfg.vocab_size)
        step = gpt2.build_train_step(model, tx, donate=False)
        return step.lower(params, opt_state, batch).as_text()

    assert lowered("auto") == lowered("xla")


def test_llama_7b_param_count():
    cfg = llama.LlamaConfig.llama2_7b()
    n = cfg.num_params()
    assert 6.0e9 < n < 7.5e9, n
