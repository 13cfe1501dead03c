"""The yardstick's own arithmetic: FLOPs, peaks, traffic, checksums and the
plain reference against the program at a CPU size."""

import json
import os

import numpy as np
import pytest

from perfbench import flops, readback, traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _config(name):
    with open(os.path.join(ROOT, "perfbench", "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name,params_m,gflop_per_token", [
    ("gpt2-124m", 124.4, 0.860), ("gpt2-xl", 1557.6, 10.29)])
def test_flop_function_agrees_with_the_programs_parameter_count(
        name, params_m, gflop_per_token):
    from ray_tpu.models.gpt2 import GPT2Config

    model = _config(name)
    cfg = GPT2Config(**{k: model[k] for k in (
        "vocab_size", "n_positions", "n_embd", "n_layer", "n_head")})
    assert flops.num_params(model) == cfg.num_params()
    assert flops.num_params(model) / 1e6 == pytest.approx(params_m, abs=0.05)
    per_token = flops.train_flops_per_token(model, 1024)
    assert per_token == (6 * cfg.num_params()
                         + 12 * cfg.n_layer * cfg.n_embd * 1024)
    assert per_token / 1e9 == pytest.approx(gflop_per_token, rel=2e-3)


def test_peaks_table_knows_the_chip_and_refuses_a_stranger():
    assert flops.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        flops.peaks("cpu")


def test_every_seed_gives_the_same_amount_of_work():
    spec = traffic.load(os.path.join(ROOT, "perfbench", "traffic", "job.json"))
    a = traffic.packed_rows(7, spec, 50257)
    b = traffic.packed_rows(2**31 + 11, spec, 50257)
    assert a.shape == b.shape == (spec["dataset_steps"] * spec["batch"],
                                  spec["seq"] + 1)
    assert not np.array_equal(a, b)
    assert np.array_equal(a, traffic.packed_rows(7, spec, 50257))
    sep = spec["documents"]["separator_id"]
    lengths = np.diff(np.flatnonzero(a.reshape(-1) == sep))
    # heavy-tailed: the median near the file's, a tail far past it
    assert 300 < np.median(lengths) < 520
    assert lengths.max() > 8 * np.median(lengths)
    assert a.min() >= 0 and a.max() < 50257
    blocks = traffic.dataset_blocks(7, spec, 50257)
    assert sum(len(x) for x in blocks) == len(a)
    r = traffic.resident_tokens(2**31 + 11, {"batch": 16, "seq": 1024}, 50257)
    assert r.shape == (16, 1025) and r.dtype == np.int32


def test_device_and_file_checksums_agree_and_see_a_change():
    import jax
    import jax.numpy as jnp

    from perfbench import worker

    x = np.random.default_rng(0).normal(size=(70001, 3)).astype(np.float32)
    tree = {"a": jnp.asarray(x), "count": jnp.int32(7)}
    sums = np.asarray(worker._checksums(jax, jnp)(tree)).tolist()
    assert sums == [readback.checksum(x), readback.checksum(np.int32(7))]
    y = x.copy()
    y[[5, 6]] = y[[6, 5]]  # a permutation keeps the plain sum
    assert readback.checksum(y)[0] == sums[0][0]
    assert readback.checksum(y)[1] != sums[0][1]


def test_reference_agrees_with_the_program_in_float32_and_tells_bf16():
    """CPU-size version of the comparison every chip run makes: equal in
    float32 to rounding; bfloat16 compute inside the tolerance the
    configuration files state; a coarser precision outside it."""
    import jax
    import jax.numpy as jnp

    from perfbench import reference
    from ray_tpu.models import gpt2

    with open(os.path.join(ROOT, "perfbench", "tests", "configs",
                           "tiny.json")) as f:
        model = json.load(f)
    tol = _config("gpt2-124m")["reference"]
    sizes = {k: model[k] for k in ("vocab_size", "n_positions", "n_embd",
                                   "n_layer", "n_head")}
    tokens = np.random.default_rng(1).integers(0, 512, (4, 129), dtype=np.int32)
    batch = {"input_ids": jnp.asarray(tokens[:, :-1]),
             "labels": jnp.asarray(tokens[:, 1:])}

    def system(dtype, params):
        cfg = gpt2.GPT2Config(**sizes, dtype=dtype, loss_chunks=8)
        return jax.value_and_grad(gpt2.loss_fn)(params, gpt2.GPT2(cfg), batch)

    params = gpt2.init_params(gpt2.GPT2Config(**sizes), jax.random.PRNGKey(0))[1]
    ref_loss, ref_grads = reference.over_microbatches(
        model, params, tokens, 2, True, jnp.asarray)

    def differences(dtype, p=params):
        loss, grads = system(dtype, p)
        ns, nr, cos = (float(v) for v in
                       reference.compare_gradients(grads, ref_grads))
        return (abs(float(loss) - float(ref_loss)) / float(ref_loss),
                abs(ns - nr) / nr, cos)

    d_loss, d_norm, cos = differences(jnp.float32)
    assert d_loss < 1e-6 and d_norm < 1e-5 and cos > 0.999999
    d_loss, d_norm, cos = differences(jnp.bfloat16)
    assert d_loss <= tol["loss_rel_tol"]
    assert d_norm <= tol["grad_norm_rel_tol"] and cos >= tol["grad_cosine_min"]
    # weights rounded to 3 bits of mantissa (what fp8 e4m3 keeps): outside
    coarse = jax.tree.map(
        lambda a: a.astype(jnp.float8_e4m3fn).astype(jnp.float32), params)
    d_loss, d_norm, cos = differences(jnp.bfloat16, coarse)
    assert (d_loss > tol["loss_rel_tol"] or d_norm > tol["grad_norm_rel_tol"]
            or cos < tol["grad_cosine_min"])
