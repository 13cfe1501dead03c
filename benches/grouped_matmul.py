"""The held experts' grouped matmuls alone (``ops.moe.held_expert_ffn``'s
seven a layer, six shapes): ``jax.lax.ragged_dot`` and its cotangents, which
the TPU compiler lowers to its own kernel (``ragged-dot-none``), beside the
Pallas kernels of ``ray_tpu/ops/grouped_matmul.py`` and, with ``--megablox
1``, jax's own Pallas grouped matmul (``jax.experimental.pallas.ops.tpu.
megablox``). Run on the chip; one JSON line a matmul: ``device`` (platform
and kind), ``ragged_ms``, ``kernel_ms`` at the tiles the shapes choose
(``tiles``), each the device time of the matmul's own operations in a trace
(``*_rest_ms``: the call's other operations, the layout copies a call by
itself pays), ``peak_ms`` (the arithmetic of the rows present at 197
TFLOP/s) and each one's share of it. Off a TPU it refuses to run but with
``--pallas_interpret 1``, a rehearsal of its own code on the CPU at a small
``--shape``: the kernels interpreted, ``*_wall_ms`` in place of every device
time, no ``peak_ms`` and no share of a peak, ``rehearsal`` true on the line.

    python benches/grouped_matmul.py --cells nemotron,qwen,window,latent,lfm2
    python benches/grouped_matmul.py --cells nemotron --sweep 1 --megablox 1
    python benches/grouped_matmul.py --shape 6144,98304,3072,2048,2048,8
    python benches/grouped_matmul.py --pallas_interpret 1 --check 1 \
        --cells "" --shape 300,512,256,232,128,4      # off a TPU

A cell's shape is (rows present, buffer rows, d, the way up's columns, an
expert's width, held): a SwiGLU's way up is two widths wide, nemotron's
``relu2`` one. ``--shape`` takes another; ``--sweep 1`` times the kernels at
other tiles than the rule's; ``--check 1`` holds each kernel to ``ragged_dot``
on the chip, NaN planted in every row past the count. The matmuls of a layer
are ``up`` (rows x wi, forward and again backward), ``down`` (act x wo),
``g_act`` (g_rows x wo^T), ``d_rows`` (d_hidden x wi^T), ``d_wi`` (rows^T x
d_hidden) and ``d_wo`` (act^T x g_rows). The rows present are dealt to the
groups as a levelled router deals them (a multinomial of equal shares).

Read on a v5e (PR 61, ``chiprun_out/pr61/call7/table.jsonl``: the final
kernels; ms a call, and the share of the peak)::

    cell         matmul      K     N    peak   ragged_dot     the kernel     tiles
    nemotron     up       2688  1856   0.311   2.747 (11.3%)  0.469 (66.4%)  256x1856
    nemotron     down     1856  2688   0.311   2.846 (10.9%)  0.477 (65.3%)  256x2688
    nemotron     g_act    2688  1856   0.311   2.712 (11.5%)  0.467 (66.7%)  256x1856
    nemotron     d_rows   1856  2688   0.311   2.848 (10.9%)  0.483 (64.5%)  256x2688
    nemotron     d_wi     2688  1856   0.311   3.619 ( 8.6%)  0.506 (61.5%)  256x2688x1856
    nemotron     d_wo     1856  2688   0.311   3.988 ( 7.8%)  0.491 (63.4%)  256x1856x2688
    qwen         up       2048  1024   0.218   0.748 (29.1%)  0.460 (47.4%)  256x1024
    qwen         down      512  2048   0.109   0.369 (29.5%)  0.303 (36.0%)  256x2048
    qwen         g_act    2048   512   0.109   0.335 (32.5%)  0.239 (45.5%)  256x512
    qwen         d_rows   1024  2048   0.218   0.801 (27.2%)  0.501 (43.5%)  256x2048
    qwen         d_wi     2048  1024   0.218   0.937 (23.3%)  0.540 (40.3%)  256x2048x1024
    qwen         d_wo      512  2048   0.109   0.475 (22.9%)  0.305 (35.7%)  256x512x2048
    window       up       2048  2048   0.349   0.710 (49.1%)  0.488 (71.4%)  256x2048
    window       down     1024  2048   0.174   0.381 (45.8%)  0.266 (65.5%)  256x2048
    window       g_act    2048  1024   0.174   0.323 (54.0%)  0.248 (70.4%)  256x1024
    window       d_rows   2048  2048   0.349   0.640 (54.5%)  0.487 (71.6%)  256x2048
    window       d_wi     2048  2048   0.349   0.810 (43.1%)  0.503 (69.3%)  256x2048x2048
    window       d_wo     1024  2048   0.174   0.408 (42.7%)  0.264 (66.0%)  256x1024x2048
    latent       up       2048  1536   0.262   0.674 (38.8%)  0.440 (59.5%)  256x1536
    latent       down      768  2048   0.131   0.432 (30.2%)  0.258 (50.7%)  256x2048
    latent       g_act    2048   768   0.131   0.394 (33.2%)  0.224 (58.3%)  256x768
    latent       d_rows   1536  2048   0.262   0.628 (41.7%)  0.454 (57.6%)  256x2048
    latent       d_wi     2048  1536   0.262   0.857 (30.5%)  0.483 (54.1%)  256x2048x1536
    latent       d_wo      768  2048   0.131   0.575 (22.7%)  0.261 (50.1%)  256x768x2048
    latent-full  up       2048  1536   1.482   2.487 (59.6%)  1.780 (83.3%)  256x1536
    latent-full  down      768  2048   0.741   1.631 (45.4%)  1.008 (73.5%)  256x2048
    latent-full  g_act    2048   768   0.741   1.424 (52.0%)  0.886 (83.7%)  256x768
    latent-full  d_rows   1536  2048   1.482   2.254 (65.7%)  1.827 (81.1%)  256x2048
    latent-full  d_wi     2048  1536   1.482   2.513 (59.0%)  1.771 (83.7%)  256x2048x1536
    latent-full  d_wo      768  2048   0.741   1.741 (42.5%)  0.930 (79.7%)  256x768x2048
    lfm2         up       2048  3584   2.442   3.883 (62.9%)  2.808 (87.0%)  256x3584
    lfm2         down     1792  2048   1.221   2.276 (53.6%)  1.437 (84.9%)  256x2048
    lfm2         g_act    2048  1792   1.221   2.237 (54.6%)  1.423 (85.8%)  256x1792
    lfm2         d_rows   3584  2048   2.442   3.404 (71.7%)  2.751 (88.7%)  256x2048
    lfm2         d_wi     2048  3584   2.442   3.883 (62.9%)  2.786 (87.7%)  256x1024x3584
    lfm2         d_wo     1792  2048   1.221   2.680 (45.6%)  1.393 (87.6%)  256x1792x2048

What the 8-11% of the nemotron cell was made of (``step0b_cells.jsonl``,
``ragged_dot`` alone, ms a call over the six matmuls): its widths 2,688 and
1,856 at 768 rows an expert 2.71-3.98; rounded up to 2,816 and 1,920 (whole
lane tiles, no whole 256) 1.63-2.64; to 3,072 and 2,048 0.78-1.04 (38-50% of
the peak, the other cells' range); its own widths at 4,096 rows an expert
10.2-14.4 (11.6-16.2%: five times the rows, the same share). The widths, not
the rows an expert and not the buffer's length. XLA's kernel is a Mosaic
kernel in tiles of 512 x 128 x 128 (``ragged_dot_tiling`` in the compiled
text).

jax's ``megablox`` beside the kernels (``--megablox 1``: ``gmm``, ``gmm`` with
``transpose_rhs`` and ``tgmm``, each at the best of six tilings (tm, tk, tn),
which differs by matmul: (256, 1024, 1024), (512, K, 512), (512, 512, 512),
(512, K / 3, N); ``call5/megablox_cells.jsonl``, nemotron's from
``step0b_nemotron.jsonl``; ms a call, megablox / the kernel; a layer: the
seven calls, ``up`` twice)::

    cell      up             down           g_act          d_rows         d_wi           d_wo           a layer
    nemotron  0.547 / 0.469  0.584 / 0.477  0.575 / 0.467  0.613 / 0.483  0.576 / 0.506  0.658 / 0.491   4.10 /  3.36
    qwen      0.537 / 0.460  0.365 / 0.303  0.297 / 0.239  0.504 / 0.501  0.583 / 0.540  0.464 / 0.305   3.29 /  2.81
    window    0.534 / 0.488  0.257 / 0.266  0.271 / 0.248  0.536 / 0.487  0.562 / 0.503  0.282 / 0.264   2.98 /  2.75
    latent    0.538 / 0.440  0.301 / 0.258  0.320 / 0.224  0.548 / 0.454  0.646 / 0.483  0.336 / 0.261   3.23 /  2.56
    lfm2      2.828 / 2.808  1.424 / 1.437  1.625 / 1.423  3.366 / 2.751  3.025 / 2.786  1.536 / 1.393  16.63 / 15.41

It gives the same answers (rows past the groups left alone, zeros for an
empty group, any K and N) and is level with the kernels in four of the thirty
(qwen's ``d_rows`` and lfm2's ``up`` within 1%, window's and lfm2's ``down``
1-3% ahead) and 7-43% behind in the others (qwen's ``d_wo`` 52%): its blocks
are what the compiler's own VMEM limit takes (the widest tilings end in
RESOURCE_EXHAUSTED), so the rows are read again for each block of columns or K
is summed over blocks. Over a step that is 0.3% (window) to 1.0% (lfm2) of
``device_step_ms``, 0.7% in the nemotron cell, and it would still need a rule
that picks a tiling by shape (at (512, K / 3, 128), near its default, it reads
2-4 times the kernel): the repo holds its own. ``tgmm`` takes the rows as
``[K, R]``; a lone call showed no transposition pass (the call's other
operations 0.02-0.04 ms), what a step would pay for it was not read.

What the tiles rest on (``--sweep 1``: ``step0b_nemotron.jsonl``,
``step0b_sweeps.jsonl``; read with every block multiplied whole). Rows a tile:
128 and 256 read the same at 768 rows an expert (0.457 / 0.459: fewer masked
rows against a worse fill of the MXU), 128 is 1-6% ahead at 320 rows an expert
(qwen) and 1-2% behind at 4,096 (lfm2); 512 is 10-25% behind at 768 and 320:
one tile of 256 (``_ROWS_A_TILE``). Columns: all in one block wherever VMEM
takes them (nemotron up 0.459 at 1,856 against 0.487-0.583 in blocks of 640,
1,024 or 512: the rows are read again for every block of columns); blocks that
cover the columns with none to spare (lfm2 d_wi 2.79 at K in 2 x 1,024 against
3.16 in 3 x 768). The weight gradients written in float32 by the kernel, as
the parameters are, read 0.60-0.62 ms at the nemotron shape where bfloat16
reads 0.49-0.51 (twice the bytes, and AdamW's fusion reads the bfloat16 as it
is): not kept.

The columns a pass (``_COLUMNS_A_PASS``; ``call6/passes_*.jsonl`` beside the
whole blocks of ``table.jsonl``, PR 61's first): ``by_group``'s body as a loop
over its block's columns, so that Mosaic compiles one pass and not the block
unrolled (a kernel compiles for a described v5e, on a CPU, in 0.3-0.5 s at
passes of 512 and 0.3-0.45 at 256 where whole blocks take 0.5-1.6). Passes of
512 cost a call 2-7% (nemotron up 0.459 -> 0.468, qwen down 0.282 -> 0.303,
lfm2 up 2.695 -> 2.808), passes of 256 4-15% (0.482, 0.324, 2.912); a step
shows neither (lfm2's traced ``device_step_ms`` 470.97 whole, 470.83 at 512).
``per_group`` in passes of 512 lost 3-12% (lfm2 d_wi 2.786 -> 3.116; 3.507 at
256: the rows are masked again every pass) and stands in no program but the
step: whole.
"""

import argparse
import importlib
import json
import os
import re
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

PEAK_FLOPS = 197e12          # one v5e chip, bfloat16
# a grouped matmul's own operations in a trace: XLA's, this repo's, megablox's
MATMUL = re.compile(r"%?(?:ragged-dot|grouped_matmul|t?gmm)")

# (rows present, buffer rows, d, the way up's columns, width, held)
CELLS = {
    "nemotron": (6144, 98304, 2688, 1856, 1856, 8),
    "qwen": (10240, 163840, 2048, 1024, 512, 32),
    "window": (8192, 131072, 2048, 2048, 1024, 8),
    "latent": (8192, 131072, 2048, 1536, 768, 16),
    "latent-full": (46400, 131072, 2048, 1536, 768, 16),
    "lfm2": (32768, 131072, 2048, 3584, 1792, 8),
    # the nemotron cell's with its widths rounded up to whole tiles of 256 or
    # 512, and with 4,096 rows an expert
    "nemotron-2816": (6144, 98304, 2816, 1920, 1920, 8),
    "nemotron-3072": (6144, 98304, 3072, 2048, 2048, 8),
    "nemotron-4096-rows": (32768, 98304, 2688, 1856, 1856, 8),
}


def matmuls(d, up, width):
    """name -> (form, K, N): the contraction's width and the result's."""
    return {"up": ("rows", d, up), "down": ("rows", width, d),
            "g_act": ("rows_t", d, width), "d_rows": ("rows_t", up, d),
            "d_wi": ("matrices", d, up), "d_wo": ("matrices", width, d)}


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--cells", default="nemotron")
    parser.add_argument("--shape", default="")
    parser.add_argument("--only", default="")
    parser.add_argument("--reps", type=int, default=5)
    parser.add_argument("--sweep", type=int, default=0)
    parser.add_argument("--megablox", type=int, default=0)
    parser.add_argument("--check", type=int, default=0)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--pallas_interpret", type=int, default=0,
                        help="1: a rehearsal on the CPU (no device time)")
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import grouped_matmul as gm
    from ray_tpu.ops import mosaic

    device = {"platform": jax.devices()[0].platform,
              "kind": jax.devices()[0].device_kind}
    interpret = bool(args.pallas_interpret)
    if interpret == (jax.default_backend() == "tpu"):
        sys.exit(f"benches/grouped_matmul.py reads device times on a TPU; "
                 f"this is {device}: --pallas_interpret 1 rehearses it off "
                 "one, and only there")
    if interpret:
        # the tiles a v5e would choose: the kernels' budget is its VMEM
        mosaic.device_kind = lambda: "TPU v5 lite"
    ms = "wall_ms" if interpret else "ms"
    bf16 = jnp.bfloat16

    def timed(fn, *xs):
        """(ms of the grouped matmul's own operations a call, ms of the
        call's other operations), on the device, from a trace of ``reps``
        calls: a jitted call alone hands XLA operands and a result in the
        layouts it likes for a buffer by itself (a width that is no whole
        lane tile is not left minor: ``bf16[98304,1856]{0,1}``), and the
        copies to and from the kernel's layout, 1.1-1.4 ms at the nemotron
        shape, are no part of a step, where the neighbours are kernels and
        loops that take the rows as they are. A rehearsal: the wall time of
        one call, written under ``*_wall_ms``."""
        jax.block_until_ready(fn(*xs))
        if interpret:
            start = time.perf_counter()
            jax.block_until_ready(fn(*xs))
            return round((time.perf_counter() - start) * 1e3, 4), 0.0
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
        own = sum(end - start for name, start, end in ops
                  if MATMUL.match(xplane.short_name(name)))
        rest = sum(end - start for name, start, end in ops
                   if not xplane.short_name(name).startswith(
                       xplane._CONTROL_FLOW)) - own
        return (round(own / args.reps / 1e6, 4),
                round(rest / args.reps / 1e6, 4))

    def attempt(fn, *xs):
        try:
            return timed(fn, *xs)
        except Exception as e:                        # a tiling it refuses
            return str(e).splitlines()[0][:160], None

    shapes = {name: CELLS[name] for name in args.cells.split(",") if name}
    if args.shape:
        shapes[args.shape] = tuple(int(n) for n in args.shape.split(","))
    for cell, (present, length, d, up, width, held) in shapes.items():
        rng = np.random.default_rng(args.seed)
        sizes = jnp.asarray(rng.multinomial(present, [1 / held] * held),
                            jnp.int32)
        live = (jnp.arange(length) < present)[:, None]
        for name, (form, k, n) in matmuls(d, up, width).items():
            if args.only and name not in args.only.split(","):
                continue
            keys = jax.random.split(jax.random.PRNGKey(args.seed), 2)
            rows = jax.random.normal(keys[0], (length, k), bf16)
            if form == "matrices":
                other = jax.random.normal(keys[1], (length, n), bf16)
                ragged = jax.jit(lambda rows, other, sizes: jax.vjp(
                    lambda m: jax.lax.ragged_dot(rows, m, sizes),
                    jnp.zeros((held, rows.shape[1], other.shape[1]), bf16)
                )[1](other)[0])
                tiles = gm.tiles_per_group(rows, other)
                kernel = lambda tiles: jax.jit(
                    lambda rows, other, sizes: gm.per_group(
                        rows, other, sizes, tiles=tiles, interpret=interpret))
                sweep = [(tile, bk, bn) for tile in (256, 512)
                         for bk in sorted({k, -(-k // 256) * 128,
                                           -(-k // 384) * 128})
                         for bn in sorted({n, -(-n // 256) * 128,
                                           -(-n // 384) * 128})]
            else:
                shape = (held, n, k) if form == "rows_t" else (held, k, n)
                other = jax.random.normal(keys[1], shape, bf16) * 0.02
                if form == "rows_t":
                    ragged = jax.jit(lambda rows, w, sizes: jax.lax.ragged_dot(
                        rows, w.swapaxes(1, 2), sizes))
                else:
                    ragged = jax.jit(jax.lax.ragged_dot)
                tiles = gm.tiles_by_group(rows, other, form == "rows_t")
                kernel = lambda tiles: jax.jit(
                    lambda rows, w, sizes: gm.by_group(
                        rows, w, sizes, transposed=form == "rows_t",
                        tiles=tiles, interpret=interpret))
                sweep = [(tile, cols) for tile in (128, 256, 512)
                         for cols in sorted({n, -(-n // 256) * 128,
                                             -(-n // 384) * 128,
                                             -(-n // 512) * 128})]
            line = {"cell": cell, "matmul": name, "form": form,
                    "device": device, "present": present, "rows": length,
                    "k": k, "n": n, "held": held, "tiles": tiles}
            if interpret:
                line["rehearsal"] = True
            else:
                line["peak_ms"] = round(
                    2 * present * k * n / PEAK_FLOPS * 1e3, 4)

            def read(who, fn):
                line[f"{who}_{ms}"], rest = timed(fn, rows, other, sizes)
                if not interpret:
                    line[f"{who}_rest_ms"] = rest
                    line[f"{who}_pct"] = round(
                        100 * line["peak_ms"] / line[f"{who}_ms"], 1)

            read("ragged", ragged)
            if tiles is not None:
                read("kernel", kernel(tiles))
            if args.check and tiles is not None:
                marked = jnp.where(live, rows, jnp.nan)
                second = (jnp.where(live, other, jnp.nan)
                          if form == "matrices" else other)
                want = ragged(jnp.where(live, rows, 0),
                              jnp.where(live, other, 0)
                              if form == "matrices" else other, sizes)
                got = kernel(tiles)(marked, second, sizes)
                if form != "matrices":
                    want, got = want[:present], got[:present]
                err = float(jnp.abs(got.astype(jnp.float32)
                                    - want.astype(jnp.float32)).max())
                line["check_max_abs_err"] = err
                line["check_scale"] = float(
                    jnp.abs(want.astype(jnp.float32)).max())
                assert np.isfinite(err) and err <= 0.02 * line[
                    "check_scale"] + 1e-6, line
            if args.sweep:
                line["sweep_" + ms] = {
                    ",".join(map(str, t)): attempt(kernel(t), rows, other,
                                                   sizes)[0]
                    for t in sweep if t != tiles}
            if args.megablox:
                megablox = importlib.import_module(
                    "jax.experimental.pallas.ops.tpu.megablox.gmm")

                def theirs(tiling):
                    if form == "matrices":
                        return jax.jit(lambda rows, other, sizes: megablox.tgmm(
                            rows.T, other, sizes, bf16, tiling))
                    return jax.jit(lambda rows, w, sizes: megablox.gmm(
                        rows, w, sizes, bf16, tiling,
                        transpose_rhs=form == "rows_t"))
                # [its own operations, the call's others]: ``tgmm`` takes
                # the rows transposed, a pass over the buffer of its own
                line["megablox_" + ms] = {
                    ",".join(map(str, t)): attempt(theirs(t), rows, other,
                                                   sizes)
                    for t in ((512, 128 * (k // 128 // 3 or 1), 128),
                              (512, 512, 512), (512, 1024, 1024),
                              (256, 1024, 1024), (512, k, 512),
                              (512, 128 * (k // 128 // 3 or 1), n))}
            print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
