"""The model whose attention sees the keys a learned indexer picks
(``ray_tpu.models.keye``), held to the plain reference
``perfbench/families/keye_reference.py`` at small sizes on the CPU, seeded
weights, no cluster: positions, both loss terms and every gradient leaf with
``topk`` under the length, all experts held and one share of four; which
loss moves which leaves (the shares of a layer of this kind against the
uncut layer, all 8 of them, are ``tests/test_sdar.py``'s parametrised case:
the same layer); the configuration file held to the published keys; the benchmark family's step
as the worker calls it."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import worker
from ray_tpu._private import steptrace
from ray_tpu.models import keye

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _json(*path):
    with open(os.path.join(ROOT, *path)) as f:
        return json.load(f)


TOY = _json("perfbench", "tests", "configs", "tiny-keye.json")
CELL = _json("perfbench", "configs", "keye-vl-2.0-30b-a3b.json")
TRAFFIC = _json("perfbench", "tests", "traffic", "step-keye.json")
REFERENCE = worker.load_reference(ROOT, TOY)
FAMILY = worker.load_family(ROOT, TOY)


def _tokens(seed, vocab=TOY["vocab_size"], batch=4, seq=64):
    return np.random.default_rng(seed).integers(
        0, vocab, (batch, seq + 1), dtype=np.int32)


# ----------------------------------------------------------------------
# the model against the reference
# ----------------------------------------------------------------------

LAYOUT = {"seq": 48, "images": 2, "image_grid": [2, 4],
          "image_offsets": [8, 30]}


def _small(seq=48, batch=2, **kw):
    config = keye.KeyeConfig.small_test(dtype=jnp.float32,
                                        index_dtype=jnp.float32, **kw)
    model, params = keye.init_params(config, jax.random.PRNGKey(1))
    # norms' scales away from one, so that a misplaced norm shows
    keys = iter(jax.random.split(jax.random.PRNGKey(2), 64))
    params = jax.tree.map(
        lambda x: x + 0.1 * jax.random.normal(next(keys), x.shape)
        if x.ndim == 1 and x.shape[0] != config.num_experts else x, params)
    tokens = _tokens(5, config.vocab_size, batch, seq)
    ids, image = keye.image_layout(seq, LAYOUT["image_offsets"],
                                   LAYOUT["image_grid"])
    text = np.append(~image[1:], True).astype(np.float32)
    return config, model, params, {
        "input_ids": jnp.asarray(tokens[:, :-1]),
        "labels": jnp.asarray(tokens[:, 1:]),
        "position_ids": jnp.broadcast_to(ids[:, None], (3, batch, seq)),
        "loss_weights": jnp.broadcast_to(text, (batch, seq))}


def _as_reference(config):
    index, of = config.expert_shard
    keys = ("vocab_size", "hidden_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "moe_intermediate_size", "num_experts_per_tok", "norm_topk_prob",
            "rope_theta", "rms_norm_eps")
    return {**{k: getattr(config, k) for k in keys},
            "expert_shard": {"index": index, "of": of},
            "rope_scaling": {"mrope_section": list(config.mrope_section)},
            "sa_config": {"indexer_num_heads": config.indexer_num_heads,
                          "indexer_head_dim": config.indexer_head_dim,
                          "topk": config.topk}}


def test_the_positions_are_the_references_and_the_familys():
    """Two copies of one rule (the program's, which the adapter hands the
    step as a user's loader would, and the reference's own), and the rule by
    hand: a span of 2 x 4 at 8 takes (8, 8 + r, 8 + c), the text
    after it goes on from 8 + max(2, 4)."""
    ids, image = keye.image_layout(48, [8, 30], (2, 4))
    theirs, their_image = REFERENCE.positions(LAYOUT, 48)
    mine, text = FAMILY.layout_of(dict(LAYOUT, seq=48))
    np.testing.assert_array_equal(ids, theirs)
    np.testing.assert_array_equal(ids, mine)
    np.testing.assert_array_equal(image, their_image)
    np.testing.assert_array_equal(text, np.append(~image[1:], True))
    np.testing.assert_array_equal(ids[:, 7:17].T, [
        [7, 7, 7], [8, 8, 8], [8, 8, 9], [8, 8, 10], [8, 8, 11], [8, 9, 8],
        [8, 9, 9], [8, 9, 10], [8, 9, 11], [12, 12, 12]])
    assert image.sum() == 16 and text.sum() == 48 - 16


@pytest.mark.parametrize("shard", [(1, 4)], ids=["one_of_4"])
def test_the_model_is_the_reference(shard):
    """Both loss terms and every gradient leaf, ``topk`` 8 of 48 positions,
    the indexer's leaves among them; the sets themselves. One share of
    four: the float32 rehearsal through ``run.py``
    (``tests/test_perfbench_keye.py``) holds the family's step to the
    reference again, at the toy configuration's own share."""
    config, model, params, batch = _small(expert_shard=shard)
    m = _as_reference(config)
    held = config.experts_held
    for name in (f"layers_{i}" for i in range(config.num_hidden_layers)):
        moe = params[name]["moe"]
        params[name]["moe"] = {**moe, "experts_wi": moe["experts_wi"][:held],
                               "experts_wo": moe["experts_wo"][:held]}
    (loss, aux), grads = jax.jit(jax.value_and_grad(
        lambda p: keye.loss_fn(p, model, batch), has_aux=True))(params)
    triples = np.asarray(batch["position_ids"][:, 0])
    with jax.default_matmul_precision("highest"):
        (want, (lm, index)), want_grads = jax.jit(jax.value_and_grad(
            lambda p: REFERENCE.loss(
                p, batch["input_ids"], batch["labels"],
                batch["loss_weights"], triples, m=m), has_aux=True))(params)
    np.testing.assert_allclose(loss, want, rtol=1e-6)
    np.testing.assert_allclose(aux["lm_loss"], lm, rtol=1e-6)
    np.testing.assert_allclose(aux["index_loss"], index, rtol=1e-5)
    assert float(index) > 0
    flat = jax.tree_util.tree_leaves_with_path(grads)
    for (path, got), want_leaf in zip(flat, jax.tree.leaves(want_grads)):
        scale = float(jnp.abs(want_leaf).max()) or 1.0
        np.testing.assert_allclose(
            got / scale, want_leaf / scale, atol=2e-5,
            err_msg=jax.tree_util.keystr(path))
    said = jax.jit(lambda p: model.apply(
        {"params": p}, batch["input_ids"], batch["position_ids"],
        mutable=["intermediates"])[1])(params)
    for i, theirs in enumerate(REFERENCE.selections(
            params, batch["input_ids"], triples, m=m)):
        ours = said["intermediates"][f"layers_{i}"]["attn"]["selected"][0]
        np.testing.assert_array_equal(jnp.swapaxes(ours, 1, 2) != 0, theirs)
        # at least topk a query: scores that tie with the last are kept
        assert int(theirs.sum()) >= 2 * (36 + 40 * 8)


def test_each_loss_moves_its_own_leaves():
    """``L_I``'s gradient reaches the indexer alone and ``L_lm``'s none of
    it (the router's selection bias takes none from either)."""
    config, model, params, batch = _small()
    term = lambda name: jax.jit(jax.grad(
        lambda p: keye.loss_fn(p, model, batch)[1][name]))(params)
    for (path, lm), index in zip(
            jax.tree_util.tree_leaves_with_path(term("lm_loss")),
            jax.tree.leaves(term("index_loss"))):
        name = jax.tree_util.keystr(path)
        lm, index = float(jnp.abs(lm).max()), float(jnp.abs(index).max())
        if "index_" in name:
            assert lm == 0 and index > 0, name
        elif "router_bias" in name:
            assert lm == index == 0, name
        else:
            assert lm > 0 and index == 0, name


def test_a_traced_pass_says_what_it_built():
    config, model, params, batch = _small()
    steptrace.set_enabled(True)
    steptrace.reset()
    try:
        jax.eval_shape(lambda p: keye.loss_fn(p, model, batch), params)
        counters = [e for e in steptrace.chrome_trace(
            steptrace.merge_records(steptrace.snapshot())) if e["ph"] == "C"]
    finally:
        steptrace.set_enabled(False)
    by_name = {e["name"]: e["args"] for e in counters}
    assert by_name["model/layer_kinds"] == {
        "sparse": 2, "expert": 2, "layers": 2, "published_layers": 48,
        "topk": 8}
    assert by_name["rope/table"]["kind"] == "sections"
    assert [by_name["rope/table"][f"section_{c}"] for c in "thw"] == [2, 3, 3]
    pairs = 2 * (36 + 40 * 8)
    assert by_name["attn/selected"] == {
        "topk": 8, "rows": 96, "heads": 4, "pairs_selected": pairs,
        "pairs_causal": 2 * 48 * 49 // 2, "dead_tiles": 0, "kernel": 0}
    assert by_name["index/scores"]["pairs"] == 2 * 48 * 49 // 2
    assert by_name["index/scores"]["flops_needed"] == 2 * 48 * 49 * 4 * 8
    assert by_name["index/scores"]["operand_bits"] == 32
    assert by_name["index/threshold"]["passes"] == 1
    # the mask as it is kept: a bit a pair, 48 keys in one chunk's 8 rows of
    # words a query
    assert by_name["index/kept"] == {
        "bits_a_pair": 1, "bytes": 2 * 8 * 48 * 4, "kernel": 0}
    assert by_name["index/loss"]["main_heads"] == 4
    assert by_name["attention/head_rotary"]["rotated"] == 1


# ----------------------------------------------------------------------
# the configuration file and the family's counts
# ----------------------------------------------------------------------

def test_the_configuration_is_the_published_one_but_for_the_cut():
    cut = {"num_hidden_layers": 6, "num_experts": 16,
           "vocab_size": 151936 // 8}
    assert {k: CELL[k] for k in cut} == cut
    assert CELL["published"] == {"num_hidden_layers": 48, "num_experts": 128,
                                 "vocab_size": 151936}
    assert set(CELL["reduced_note"]) == set(cut)
    bench = _json("BENCHMARK.json")
    entry = next(c for c in bench["configs"]
                 if c["name"] == "keye-vl-2.0-30b-a3b")
    assert entry["reduced"] == list(cut) and entry["source"] == CELL["source"]
    assert CELL["expert_shard"] == {"index": 0, "of": 8}
    assert "8 v5e chips share each layer" in CELL["deployment"]
    assert {"qk_norm", "rotary", "positions", "images", "indexer",
            "indexer_rotation", "chunk_sizes", "selection", "index_loss",
            "lm_loss", "initializer_range", "router_bias",
            "optimizer"} <= set(CELL["assumed"])
    assert CELL["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512, "q_chunk_size": 512,
        "topk": 2048}
    assert CELL["rope_scaling"]["mrope_section"] == [16, 24, 24]
    assert CELL["rope_theta"] == 10_000_000
    assert any("exactly its 2,048" in g for g in CELL["guarantees"])
    assert CELL["reference"]["layout"] == "perfbench/traffic/step-16k-img.json"
    # every key of the catalog's row under its name, but the three cut
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(json.loads(line) for line in f
                       if '"Keye-VL-2.0-30B-A3B"' in line)
        assert row["source_url"] == CELL["source"]
        assert {k for k, v in row["config"].items()
                if CELL.get(k) != v} == set(cut)


def test_the_familys_counts_are_the_programs_and_a_hand_count():
    traffic = _json("perfbench", "traffic", "step-16k-img.json")
    built = FAMILY.build(CELL, traffic, None)
    params, _ = jax.eval_shape(built.make_state, jax.random.PRNGKey(0))
    made = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    indexer = 2_097_152 + 131_072 + 32_768 + 128
    layer = (2 * 8_388_608 + 2 * 1_048_576 + 256 + 4_096 + 262_144 + 128
             + indexer + 16 * 4_718_592)
    assert layer == 96_899_584
    assert FAMILY.num_params(CELL) == made == 659_190_784 == (
        6 * layer + 2 * 38_895_616 + 2_048)
    # operations a token: six layers (q, o, k, v, the indexer's three, the
    # router, one expert of the eight a token takes: 16 of 128 held), the
    # head, and the attention's three parts
    a_token = (2 * 8_388_608 + 2 * 1_048_576 + indexer - 128 + 262_144
               + 4_718_592)
    matmuls = 6 * (38_895_616 + 6 * a_token)
    chosen, causal = 31_458_304, 16384 * 16385 // 2
    assert FAMILY.selected_pairs(16384, 2048) == chosen
    attention = 6 * (12 * 4096 * chosen + 2 * 1024 * causal
                     + (2 * 4096 + 6 * 1024) * chosen) / 16384
    assert FAMILY.train_flops_per_token(CELL, 16384) == matmuls + attention
    assert attention / (matmuls + attention) == pytest.approx(0.41, abs=0.01)
    # the layout: four spans of 32 x 32, a quarter of the sequence
    ids, text = FAMILY.layout_of(traffic)
    assert text.sum() == 16384 - 4096 and ids.shape == (3, 16384)
    assert ids[:, 1024 + 33].tolist() == [1024, 1025, 1025]
    assert ids[:, 2048].tolist() == [1024 + 32] * 3
    # the toy's count is its state's too, and a traffic that is not the
    # reference's layout is refused
    toy = FAMILY.build(TOY, TRAFFIC, None)
    params, _ = jax.eval_shape(toy.make_state, jax.random.PRNGKey(0))
    assert FAMILY.num_params(TOY) == sum(
        int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    with pytest.raises(ValueError, match="layout"):
        FAMILY.build(TOY, dict(TRAFFIC, image_offsets=[8]), None)
