"""The four kernels of a layer under a learned selection, alone, at the keye
cell's shapes (1 x 16,384 tokens, 32 query heads on 4 of 128, an indexer of
16 heads of 64 that picks 2,048 keys a query): ``index_select_top2048``,
``flash_fwd_sel2048``, ``flash_bwd_sel2048`` and ``index_kl``
(``ops/sparse_index.py``, ``ops/flash_kernels.py``), PR 68.

    chiprun -- python benches/selected_attention.py --check 1

prints one JSON line: device time a call from a trace of ``--reps`` calls of
each kernel, the mask's bytes as ``select`` hands it over, and a digest of
every result (the set itself, as the dense int8 mask; the thresholds; O and
the log-sum-exp; dQ^T, dK, dV; the KL and its three gradients): two trees
whose lines carry the same digests for the same ``--seed`` did the same
work to the bit, whatever form the mask has between the kernels. ``--check
1`` also holds the flash pair under the selection to the dense masked
softmax and its ``vjp`` ON THE CHIP at ``--check-length`` tokens (8 heads on
2), which interpret mode cannot: the words' blocks come through the
pipeline and are expanded in registers. Off a TPU it exits 1 unless
``--pallas_interpret 1``: a rehearsal of the bench's own code at 1,024
tokens whose line says ``rehearsal`` and holds wall times only.

Read on a v5e: PERF.md section 6, PR 68.
"""

import argparse
import functools
import hashlib
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# (tokens, query heads, key-value heads, head width, index heads, index
# width, keys a query)
CELL = (16384, 32, 4, 128, 16, 64, 2048)
REHEARSAL = (1024, 2, 1, 128, 4, 16, 100)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--reps", type=int, default=3)
    parser.add_argument("--check", type=int, default=0)
    parser.add_argument("--check-length", type=int, default=4096)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--pallas_interpret", type=int, default=0,
                        help="1: a rehearsal on the CPU (no device time)")
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import flash_kernels, sparse_index
    from ray_tpu.ops.attention import attention_reference

    device = {"platform": jax.devices()[0].platform,
              "kind": jax.devices()[0].device_kind}
    interpret = bool(args.pallas_interpret)
    if interpret == (jax.default_backend() == "tpu"):
        sys.exit(f"benches/selected_attention.py reads device times on a "
                 f"TPU; this is {device}: --pallas_interpret 1 rehearses it "
                 "off one, and only there")
    if interpret:
        flash_kernels._MAX_RESIDENT = 512
    ms = "wall_ms" if interpret else "ms"
    bf16, f32 = jnp.bfloat16, jnp.float32

    def timed(fn, *xs):
        """ms a call of everything the device ran for it, from a trace of
        ``reps`` calls; a rehearsal: one call's wall time."""
        jax.block_until_ready(fn(*xs))
        if interpret:
            start = time.perf_counter()
            jax.block_until_ready(fn(*xs))
            return round((time.perf_counter() - start) * 1e3, 4)
        from perfbench import xplane

        trace_dir = tempfile.mkdtemp()
        try:
            with jax.profiler.trace(trace_dir):
                for _ in range(args.reps):
                    out = fn(*xs)
                jax.block_until_ready(out)
            ops = xplane.load(xplane.find_xplane(trace_dir)).ops.get(0, ())
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        busy = sum(end - start for name, start, end in ops
                   if not xplane.short_name(name).startswith(
                       xplane._CONTROL_FLOW))
        return round(busy / args.reps / 1e6, 4)

    def digest(x):
        return hashlib.sha256(np.asarray(x).tobytes()).hexdigest()[:16]

    def layer(seq, heads, kv_heads, d, index_heads, width, topk):
        """The four calls as a layer makes them and their operands."""
        keys = jax.random.split(jax.random.PRNGKey(args.seed % 2**32), 7)
        normal = lambda key, *dims: jax.random.normal(key, dims, f32)
        q_idx = normal(keys[0], 1, seq, index_heads, width).astype(bf16)
        k_idx = normal(keys[1], 1, seq, width).astype(bf16)
        w = normal(keys[2], 1, seq, index_heads) * (
            index_heads * width) ** -0.5
        qf = normal(keys[3], heads, seq, d).astype(bf16)
        kf = normal(keys[4], kv_heads, seq, d).astype(bf16)
        v = normal(keys[5], kv_heads, seq, d).astype(bf16)
        do = normal(keys[6], 1, seq, heads * d).astype(bf16)
        flash = dict(causal=True, sm_scale=d ** -0.5, block_q=None,
                     block_k=None, interpret=interpret, heads=heads,
                     topk=topk)
        impl = "pallas_interpret" if interpret else "pallas"
        select = jax.jit(lambda q_idx, k_idx, w: sparse_index.select(
            q_idx, k_idx, w, topk, impl=impl))
        fwd = jax.jit(lambda qf, kf, v, mask: flash_kernels._flash_pallas(
            qf, kf, v, selected=mask, **flash))
        bwd = jax.jit(lambda qf, kf, v, do, lse, out, mask:
                      flash_kernels._flash_pallas_bwd_kernel(
                          qf, kf, v, do, lse, out, dq_turned=False,
                          selected=mask, **flash))
        kl = jax.jit(jax.value_and_grad(
            lambda q_idx, k_idx, w, chosen, qf, kf, lse:
            sparse_index.index_kl(q_idx, k_idx, w, chosen, qf, kf, lse,
                                  topk=topk, sm_scale=d ** -0.5, impl=impl),
            argnums=(0, 1, 2)))
        return (q_idx, k_idx, w, qf, kf, v, do), (select, fwd, bwd, kl)

    (q_idx, k_idx, w, qf, kf, v, do), (select, fwd, bwd, kl) = layer(
        *(REHEARSAL if interpret else CELL))
    line = {"tokens": qf.shape[1], "heads": qf.shape[0],
            "kv_heads": kf.shape[0], "device": device, "seed": args.seed}
    if interpret:
        line["rehearsal"] = True
    line[f"index_select_{ms}"] = timed(select, q_idx, k_idx, w)
    chosen = select(q_idx, k_idx, w)
    line["mask"] = {"dtype": str(chosen.mask.dtype),
                    "shape": list(chosen.mask.shape),
                    "bytes": chosen.mask.size * chosen.mask.dtype.itemsize}
    line[f"flash_fwd_{ms}"] = timed(fwd, qf, kf, v, chosen.mask)
    out, lse = fwd(qf, kf, v, chosen.mask)
    line[f"flash_bwd_{ms}"] = timed(bwd, qf, kf, v, do, lse, out, chosen.mask)
    grads = bwd(qf, kf, v, do, lse, out, chosen.mask)
    line[f"index_kl_{ms}"] = timed(kl, q_idx, k_idx, w, chosen, qf, kf, lse)
    loss, index_grads = kl(q_idx, k_idx, w, chosen, qf, kf, lse)
    dense = sparse_index.unpack(chosen.mask)
    line["selected_pairs"] = int(dense.sum(dtype=jnp.int32))
    line["kl"] = float(loss)
    line["digests"] = {
        "set": digest(dense), "tau": digest(chosen.tau),
        "index_lse": digest(chosen.lse), "out": digest(out),
        "lse": digest(lse), "kl": digest(loss),
        **{name: digest(g) for name, g in zip(("dq_t", "dk", "dv"), grads)},
        **{name: digest(g) for name, g in zip(
            ("d_q_idx", "d_k_idx", "d_w"), index_grads)}}
    del dense, out, lse, grads

    if args.check:
        seq = 1024 if interpret else args.check_length
        heads, kv_heads = (2, 1) if interpret else (8, 2)
        _, _, _, d, index_heads, width, _ = REHEARSAL if interpret else CELL
        (q_idx, k_idx, w, qf, kf, v, do), (select, fwd, bwd, _) = layer(
            seq, heads, kv_heads, d, index_heads, width, seq // 8)
        mask = select(q_idx, k_idx, w).mask
        out, lse = fwd(qf, kf, v, mask)
        dq_t, dk, dv = bwd(qf, kf, v, do, lse, out, mask)
        # the dense form in float32, heads first; O and its cotangent are
        # the model's [1, T, H x d], dK and dV its [1, T, G x d]
        heads_of = lambda t, n: t.reshape(seq, n, d).swapaxes(0, 1)
        reference = functools.partial(
            attention_reference, causal=True,
            selected=sparse_index.unpack(mask))
        want, vjp = jax.vjp(
            lambda q, k, v: reference(q[None], k[None], v[None])[0],
            qf.astype(f32), kf.astype(f32), v.astype(f32))
        wants = (want, *vjp(heads_of(do[0], heads).astype(f32)))
        gots = (heads_of(out[0], heads), dq_t.swapaxes(1, 2),
                heads_of(dk[0], kv_heads), heads_of(dv[0], kv_heads))
        for name, got, want in zip(("out", "dq", "dk", "dv"), gots, wants):
            err = float(jnp.abs(got.astype(f32) - want).max())
            size = float(jnp.abs(want).max())
            line[f"check_{name}_max_abs_err"] = err
            line[f"check_{name}_scale"] = size
            assert err <= 0.02 * size, (name, err, size)
        line["check_tokens"] = seq
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
