"""Forward time, and forward plus backward, of causal self-attention by path,
at the head widths and lengths given: what ``ops.attention.auto_attention``
decides from. Run on the chip; prints one JSON line a shape, with the kinds
of grid block a head of the kernel's call has (``grid_block_kinds``:
``looped`` 0 where every block is walked in straight-line code) and, from a
profiler trace of three more calls, the device time of one ``flash_fwd``
and one ``flash_bwd`` alone (``flash_*_kernel_ms``), what the wall time of
forward plus backward holds besides them (``round_kernels_ms``: on the
``xla_copies`` boundary the V^T, K^T, O^T and dQ^T transposes, ``delta`` and
dQ's rounding, until PR 50 the sum of dQ's float32 partials too; on
``model_results``, since PR 55 the boundary past one block of keys at one
width of whole lane tiles, what is left of them: q's, K^T's and V^T's; on
any the bench's own product of the output with its cotangent) and the bytes
of dQ that the backward call writes (``dq_written_bytes``: its first output,
as the traced call declares it; since PR 50 one float32 [B x H, d, T] sum
where a head has several blocks of keys, where there was one such array a
block of keys; on ``model_results`` the kernel's own running sums, which
nothing reads after it).

Every ARGUMENT is in a model's layout, [B, T, H x d] dense, the output's
cotangent among them (the loss is the sum of the output times a seeded [B,
T, H x d_v] array, so that dO is an array of the program as a model's is,
not a constant that XLA folds into a copy), and the gradients come back in
it: what stands round the kernels is what a model pays (PR 51: a bench whose
arguments are lane-padded pays a relayout the model does not).

    python benches/flash_widths.py --widths 192x128 --lengths 1024,8192
    python benches/flash_widths.py --widths 128x128 --lengths 16384 \
        --kv-heads 4 --window 2048 --check 1

Tokens a call are held at ``--tokens`` (16,384: the cells' load), heads at
``--heads``, of which ``--kv-heads`` are keys' and values' (default: as
many), under ``--window`` keys (default: none). ``xla`` is what
``causal_self_attention(..., "xla")`` runs; it is skipped where its
[B, H, T, T] scores would not fit (past 4096). ``--check 1`` also holds the
kernel's output and three gradients (bfloat16 operands) against
``attention_reference`` on the same operands in float32 at matmul precision
highest, one query head at a time, and prints the largest differences
over the largest reference entry: what interpret mode cannot show of the
pipeline's writes.

Readings (v5e, one chip; the tables that stood as comments in
``ops/attention.py`` until PR 60, each with the PR whose chip run made it;
the constants they justify are ``ops/flash_kernels.py``'s ``_MAX_RESIDENT``,
``_FWD_TILES``, ``_BWD_TILES`` and ``_COPY_BYTES`` and ``ops/attention.py``'s
``_FLASH_MIN_SEQ`` and ``_FLASH_HEAD_DIMS``).

Who reaches which walk, and what a loop costs (my chip runs, PRs 25 and
38; PERF.md section 6). One block a head is every call up to 2048 tokens,
GPT-2's 1024 in four benchmark cells among them. Several blocks are any
longer sequence: the cell ``joyai-llm-flash.step-8k`` sends 8192 (4 x 4
blocks a head: 6 whole, 4 diagonal, 6 dead) and ``attention="auto"`` takes
3072, 4096 and 8192 (3.3x to 47x faster than XLA's attention there,
forward plus backward). A loop whose trip count the compiler does not
know is neither unrolled nor scheduled across: with the loop alone,
forward plus backward at 1024 take 17% longer (2.53 against 2.16 ms a
layer; ``fori_loop(..., unroll=True)`` on static bounds reads as the
Python loop does, 2.165). At 8192, 64 heads, keys 192 and values 128 wide,
a call's kernel time by walk (forward, backward; needed at the MXU's peak
6.98 and 18.14 ms): every block in loops with traced bounds 15.48 and
28.72 ms; by kind with a whole block's rows one loop 13.22 and 23.62; two
or four rows a loop step 23.42 and 23.32; a row's loop over its tiles, 1 /
2 / 4 tiles a step 26.76 / 25.11 / 24.32; every tile of a whole block
written out 13.20 and 44.73 (64 tile bodies of five matmuls on 256 lanes:
at keys 128 wide the same code reads 16.38 against 17.40 for the rows'
loop, and compiles in 10.8 s against 6.1).

``_COPY_BYTES``. The most bytes of a block of queries' float32 dQ^T sum that one DMA moves,
in a backward step over several blocks of keys (``_bwd_kernel``: a copy is
whole rows of tiles, at least one). Read on the chip (PERF.md section 6,
PR 50; ``flash_bwd`` alone at the three cells' shapes, ms, by rows of 256
queries a copy: 1 / 2 / 4 / all 8; the parent's, which wrote partials,
last): keys 192 wide at 8,192 tokens (a row 192 KiB) 23.49 / 23.48 / 23.71
/ 24.48, parent 23.62; 128 wide at 16,384 under a window of 2,048 (a row
128 KiB) 9.65 / 9.23 / 9.10 / 9.27, parent 8.92, and with no window 33.91
/ 33.59 / 33.51 / 34.20, parent 34.15; 64 wide (a row 64 KiB) 19.51 /
19.32 / 19.26 / 19.23, parent 19.28. A copy costs its start and its wait
(about 30 ns each: 32 of them a live step are 1 us), and a large one
stands in the way of the pipeline's own.

``_FLASH_MIN_SEQ``, ``_FLASH_HEAD_DIMS``. Where "auto" takes the Pallas
kernel: where it was measured faster than XLA's attention on a v5e, forward plus backward at 16,384 tokens a call
(PERF.md section 6, PR 25). Head dimension 64: every multiple of 128 tried
from 512 to 2048 (512, 640, 768, 896, 1024, 1152, 1280, 1536, 2048: 2.4x
to 4.9x; at 256 and 384 XLA wins), where one grid step holds a whole
head, and 3072, 4096 and 8192 (3.3x, 5.2x, 47x), where it holds 1536 or
2048 queries and keys. Past ``_MAX_RESIDENT`` a length that 1024 does not
divide can leave the kernel 128-wide grid blocks (2176 = 17 x 128: 20.9 ms
against XLA's 17.3), so those stay with XLA. Head dimension 128: 512, 768,
1024, 2048, 4096 (2.4x to 4.3x). The multiples of 128 between those
lengths are interpolated, lengths past 8192 extrapolated (XLA's [T, T]
scores take 718 ms a layer at 8192 and no longer fit at 16,384).
Keys 192 wide and values 128 (PR 31; 32 heads, 16,384 tokens a call,
forward plus backward, kernel against the "xla" path written out for two
widths): 512: 8.10 against 9.57 ms; 1024: 9.51 / 16.75; 2048: 13.53 /
30.57; 4096: 28.29 / 59.31 (1.2x to 2.3x); 8192: 50.66 ms, where XLA's
scores (8.6 GB) were not tried. At 128 / 128 and the same 32 heads the
kernel read 6.95, 7.86, 10.66, 25.57 and 45.59 ms.
Since PR 38 (grid blocks walked by kind; ``benches/flash_widths.py``, my
chip run, same 16,384 tokens and 32 heads), ``flash_fwd`` and ``flash_bwd``
alone in a trace, then the wall time of forward plus backward with the
transposes round them and, until PR 50, XLA's sum of dQ's partials (past
2048 tokens; what PR 50 reads at the cells' shapes stands below):
  192 / 128   2048: 3.51 and 6.11 ms, 13.53 (one block a head: as before)
              4096: 6.91 and 12.14, 24.45 (was 28.27)
              8192: 13.22 and 23.62, 43.29 (was 50.62; the kernels alone
                    15.48 and 28.72)
  128 / 128   2048: 2.65 and 4.22, 10.65; 4096: 5.23 and 8.80, 18.75 (was
              25.57); 8192: 10.12 and 17.40, 32.99 (was 45.59)
  64 / 64     1024: 1.27 and 2.60, 5.85; 4096: 4.19 and 8.04, 14.71
The wider key costs 34% to 40% more kernel time at 2048 to 8192 (less
than its 3/2 in QK^T, dK and dQ; a 192-wide operand is laid out as 256
lanes and fills the MXU's depth one and a half times). By block at 192 /
128 (the 8192 reading less four diagonal blocks a head at the 2048
reading's price): backward 23.9 us a diagonal block of 36 tiles and 45.6
a whole block of 64 (0.66 and 0.71 us a tile against 0.55 at the MXU's
peak); forward 13.7 us a diagonal block of 10 tiles and 25.3 a whole
block of 16 (1.37 and 1.58 us a tile against 0.85).
Since PR 44 (grouped key-value heads, a window; same bench with
``--kv-heads 4`` and ``--window``, my chip run, 128 / 128, one sequence of
16,384 tokens, 32 query heads, 8 x 8 grid blocks a head), ``flash_fwd`` and
``flash_bwd`` alone, then the wall time of forward plus backward:
  no window, 32 key-value heads (28 whole, 8 diagonal, 28 dead a head):
              19.47 and 34.46 ms, 60.80
  no window, 4 key-value heads: 19.18 and 34.15, 58.20 (the keys and values
              of a group are fetched once a group in the backward and leave
              as 4 heads' dK and dV: grouping costs the kernels nothing)
  window 2048, 4 key-value heads (8 diagonal, 7 trailing, 49 dead):
              6.15 and 8.92, 17.70; the dQ partials 2 x 32 x 128 x 16,384
              float32 (0.5 GiB) against 8 (2.0 GiB) without a window
              (until PR 50: below)
By kind of block, from the 2048 and 8192 readings above: a diagonal block
10.35 us forward and 16.5 backward, a whole block 19.45 and 34.3 (the full
call priced so: 20.08 and 34.96 ms, read 19.18 and 34.15); a trailing
block with the seven dead grid steps of its row 15.6 us forward (the dead
steps fetch nothing and still owe the scratch's start and the outputs'
write) and 21.0 backward. A window that is no whole number of blocks (4096
tokens under 1024 keys: "looped") reads 4.27 and 7.87 ms against 4.42 and
6.63 under 2048.
Since PR 50 (the backward sums dQ^T over a head's blocks of keys itself,
``_bwd_kernel``; same bench, my chip runs, the parent beside the change in
one call, at the three cells' shapes): ``flash_bwd`` alone, what the wall
time of forward plus backward holds besides the two kernels
(``round_kernels_ms``: the V^T, K^T, O^T and dQ^T swaps, ``delta``, dQ's
rounding; before, XLA's sum of the partials too), that wall time, and the
bytes of dQ the call writes for XLA; ``flash_fwd`` unmoved throughout:
  192 / 128 at 8,192, 64 heads:   23.62 -> 23.48 ms, 6.51 -> 4.83, 43.35 ->
              41.52; 1,610.6 -> 402.7 MB
  128 / 128 at 16,384, 32 on 4:   34.15 -> 33.51, 4.88 -> 2.22, 58.20 ->
              54.91; 2,147.5 -> 268.4 MB
    under a window of 2,048:      8.92 -> 9.10, 2.64 -> 2.22, 17.71 -> 17.48;
              536.9 -> 268.4 MB (no dead step wrote zeros here before, so
              the kernel pays for its copies and gains nothing back)
  64 / 128 at 16,384, 20 on 10:   19.28 -> 19.28, 1.91 -> 1.12, 32.84 ->
              32.05; 671.1 -> 83.9 MB
    under a window of 512:        3.73 -> 3.77, 1.19 -> 1.07, 8.02 -> 7.94
  64 / 64 at 1,024 (one block of keys a head: the kernel's body is the
              parent's): 0.975 -> 0.975, 2.09 -> 2.09
Output and the three gradients against ``attention_reference`` in float32
on the chip: the same five digits as the parent at every shape (dQ within
0.00285 to 0.00392 of the largest entry).

Keys 64 and values 128 wide (PR 48; ``benches/flash_widths.py --widths
64x128 --lengths 16384 --heads 20 --kv-heads 10 --check 1``, my chip run:
one sequence of 16,384 tokens, 20 query heads on 10 key-value heads, a map
of a differential attention layer), ``flash_fwd`` and ``flash_bwd`` alone,
then the wall time of forward plus backward; beside it (64, 64) at the same
heads, of which such a layer would need four calls where it needs two of
these:
  no window:   (64, 128) 11.65 and 19.28 ms, 32.84; (64, 64) 9.58 and
               19.28, 30.65: the wider value costs the forward 22% and the
               backward nothing that these readings show. Why not is not
               known: two of its five matmuls (dP, dV) carry the values'
               width. A guess that fits, untested: the 64-wide keys'
               passes set its time. (64, 256) and (128, 128) at the same
               heads would tell; neither was run
  window 512:  (64, 128) 3.10 and 3.73 ms, 8.01; (64, 64) 1.99 and 3.72,
               6.80. 512 keys are a quarter of a 2,048-wide grid block, so
               all 64 blocks a head are "looped" (``grid_block_kinds``):
               every diagonal block walks its tiles in loops with traced
               bounds, and the needed pairs (8.26M a head) are 10% of the
               peak forward and 20% backward. Left as it is: 14 ms of a
               785 ms step in the one cell that has such a window.
Output and the three gradients against ``attention_reference`` in float32
there: within 0.0029 to 0.0055 of the largest entry, with and without the
window (bfloat16 operands). XLA's scores at these lengths are [20, 16384,
16384] a map and were not tried.

Keys and values 256 wide (PR 56; ``benches/flash_widths.py --widths 256x256
--lengths 8192 --tokens 32768 --heads 16 --kv-heads 2 --check 1``, my chip
run: four sequences of 8,192 tokens, 16 query heads on 2 key-value heads,
the widest head and, with (32 on 4 of 128), the widest group so far): the
tiles of the narrower widths fit VMEM at twice the width, so none changed;
a head is 4 x 4 grid blocks (6 whole, 4 diagonal, 6 dead, none looped) on
the ``model_results`` boundary (one width of whole lane tiles past one
block of keys). ``flash_fwd`` 16.29 ms and ``flash_bwd`` 30.26 alone, 49.80
forward plus backward by the wall clock (3.25 of it outside the kernels);
the needed pairs are 69.5% of the MXU's peak forward and 92.1% backward
(``attn_kernel_roofline_pct``'s count on the cell's traced step, 8.03 and
15.15 ms at two sequences): a 256-wide head fills the MXU's depth where a
64-wide one fills a quarter. Output and the three gradients against
``attention_reference`` in float32 there: within 0.0030 (out), 0.0044
(dq), 0.0042 (dk), 0.0032 (dv) of the largest entry. XLA's scores at this
shape are [16, 8192, 8192] float32 a sequence, 4.3 GB, and were not tried.

A window of half a resident block (PR 62; ``benches/flash_widths.py --widths
128x128 --lengths 8192 --tokens 16384 --heads 32 --kv-heads 4 --window 1024
--check 1 [--residents 2048]``, my chip run: two sequences of 8,192 tokens,
32 query heads on 4 key-value heads, Mellum2's window layer), ``flash_fwd``
and ``flash_bwd`` alone, then the wall time of forward plus backward:
  residents 2,048 (the tree before PR 62: 1,024 keys are half a block, all
              16 blocks a head "looped"): 5.51 and 9.77 ms, 17.08
  residents 1,024 (the window one whole block: 8 diagonal, 7 trailing, 49
              dead a head): 4.48 and 6.83, 13.09; at four sequences 8.99
              and 13.69 against 11.00 and 19.56
  no window (6 whole, 4 diagonal, 6 dead): 9.95 and 17.14, 28.90
Output and the three gradients against ``attention_reference`` in float32:
the same five digits either way (within 0.0029 to 0.0038 of the largest
entry). ``_block_sizes`` takes the window's length for its residents since
(``_WINDOW_RESIDENT_FROM``); ``--residents N`` holds a step to N whatever
the window, so both readings can be made again.

A windowed call's grid holds a row's live blocks only (PR 66; my chip runs,
the parent's tree beside the change in one call, ``chiprun_out/pr66/kernels/``:
16 grid steps a head, 1 of them dead, where there were 64 and 49; the lines'
``grid_blocks`` say ``steps`` and ``dead_steps``), ``flash_fwd`` and
``flash_bwd`` alone, then the wall time of forward plus backward, parent ->
change:
  ``--widths 128x128 --lengths 8192 --tokens 16384 --heads 32 --kv-heads 4
  --window 1024 --check 1`` (Mellum2's window layer: 64 heads, residents of
              1,024): 4.478 -> 3.476 and 6.836 -> 5.769 ms, 13.10 -> 11.02
  ``--widths 128x128 --lengths 16384 --tokens 16384 --heads 32 --kv-heads 4
  --window 2048 --check 1`` (Trinity-Mini's: 32 heads, residents of 2,048):
              6.180 -> 5.444 and 9.407 -> 8.659 ms, 17.92 -> 16.47
The 48 grid steps a head that went were 3,072 a call in the first shape and
1,536 in the second: a dead step cost 0.33 us forward and 0.35 backward at
residents of 1,024, 0.48 and 0.49 at 2,048 (it started, branched, and in the
forward walked its rows' scratch: twice the rows at twice the residents).
Output and the three gradients against ``attention_reference`` in float32:
the parent's five digits at both shapes (0.00293-0.00376 and 0.00294-0.0042
of the largest entry), and with no group, where a block of queries' dQ^T
sum is written on the last step of one block of keys and fetched on the
first of the next (``--heads 4 --kv-heads 4`` at ``--lengths 8192 --tokens
8192 --window 1024`` and ``2048``, at ``--lengths 16384 --window 4096``,
three steps a row, and at ``--widths 64x128``, XLA's copies round the
kernels): dq within 0.0028-0.0047.
One reading that changes no path a cell runs, for whoever judges a lower
``_WINDOW_RESIDENT_FROM``: the hybrid cell's window (``--widths 64x128
--lengths 16384 --heads 20 --kv-heads 10 --window 512 --check 1``) as it
runs, every block ``looped`` at residents of 2,048: 3.110 and 3.772 ms,
8.67; with ``--residents 512`` under the new grid (32 x 32 blocks a head,
64 steps launched, 1 dead): 1.527 and 2.856 ms, 6.16, the same five digits
against the reference: 2.5 ms a layer's call, forward plus backward.
"""

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--widths", default="192x128,128x128")
    parser.add_argument("--lengths", default="512,1024,2048,4096,8192")
    parser.add_argument("--tokens", type=int, default=16384)
    parser.add_argument("--heads", type=int, default=32)
    parser.add_argument("--reps", type=int, default=10)
    parser.add_argument("--kv-heads", type=int, default=None)
    parser.add_argument("--window", type=int, default=None)
    parser.add_argument("--blocks", type=int, default=None,
                        help="the block-diffusion mask over two streams of "
                             "half the length each, in blocks of this many "
                             "tokens, in place of the causal mask (PR 65)")
    parser.add_argument("--check", type=int, default=0)
    parser.add_argument("--residents", type=int, default=None,
                        help="the most queries, and keys, a grid step holds "
                             "(default: the kernels' own rule)")
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import attention, flash_kernels
    from ray_tpu.ops.attention import (attention_reference,
                                       causal_self_attention,
                                       heads_a_lane_tile)
    from ray_tpu.ops.flash_kernels import grid_block_kinds

    kv_heads = args.kv_heads or args.heads
    if jax.default_backend() != "tpu":
        sys.exit("benches/flash_widths.py reads device times on a TPU; this "
                 f"is {jax.default_backend()!r}")
    if args.residents:
        # whatever the window: the rule that gives a window from
        # ``_WINDOW_RESIDENT_FROM`` keys up its own length (since PR 62)
        # takes none under the most a step holds
        flash_kernels._MAX_RESIDENT = args.residents
        flash_kernels._WINDOW_RESIDENT_FROM = args.residents

    def timed(fn, *xs):
        jax.block_until_ready(fn(*xs))
        start = time.perf_counter()
        for _ in range(args.reps):
            out = fn(*xs)
        jax.block_until_ready(out)
        return (time.perf_counter() - start) / args.reps * 1e3

    def kernel_ms(fn, *xs):
        """{"flash_fwd_kernel_ms": ..., "flash_bwd_kernel_ms": ...}: mean
        device time of a call of each kernel, found in a trace as the
        benchmark's ``attn_kernel_ms`` finds them."""
        from perfbench import xplane
        from perfbench.metrics.attn_kernel_ms import KERNEL

        trace_dir = tempfile.mkdtemp()
        try:
            with jax.profiler.trace(trace_dir):
                for _ in range(3):
                    out = fn(*xs)
                jax.block_until_ready(out)
            ops = xplane.load(xplane.find_xplane(trace_dir)).ops.get(0, ())
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        found = {}
        for name, start, end in ops:
            kernel = KERNEL.match(name)
            if kernel:
                found.setdefault(kernel.group(1), []).append(end - start)
        return {f"flash_{k}_kernel_ms": round(sum(ns) / len(ns) / 1e6, 3)
                for k, ns in found.items()}

    def dq_written_bytes(fn, *xs):
        """Bytes of the first output of the traced ``flash_bwd`` call; None
        where the path runs no kernel (off the chip)."""
        found = []

        def walk(jaxpr):
            for eqn in jaxpr.eqns:
                if (eqn.primitive.name == "pallas_call"
                        and eqn.params["name"].startswith("flash_bwd")):
                    dq = eqn.outvars[0].aval
                    found.append(dq.size * dq.dtype.itemsize)
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)

        walk(jax.make_jaxpr(fn)(*xs).jaxpr)
        return found[0] if found else None

    def against_reference(q, k, v):
        """Largest |kernel - reference| over the largest |reference| entry,
        for the output and the gradients of q, k and v under a seeded
        cotangent."""
        w = jax.random.normal(jax.random.PRNGKey(3), (*q.shape[:3],
                                                      v.shape[-1]))
        f32 = lambda x: x.astype(jnp.float32)

        def out_and_grads(fn, q, k, v, w):
            out, vjp = jax.vjp(lambda *x: f32(fn(*x)), q, k, v)
            return (out, *vjp(w))

        kernel = jax.jit(lambda q, k, v, w: out_and_grads(
            lambda *x: causal_self_attention(*x, "flash", args.window,
                                             args.blocks),
            q, k, v, w))
        bhsd = lambda t: t.transpose(0, 2, 1, 3)

        @jax.jit
        def plain(q, k, v, w):
            with jax.default_matmul_precision("highest"):
                return out_and_grads(
                    lambda *x: bhsd(attention_reference(
                        *(bhsd(t) for t in x), causal=True,
                        window=args.window, blocks=args.blocks)),
                    f32(q), f32(k), f32(v), w)

        got = kernel(q, k, v, w)
        group = args.heads // kv_heads
        want = [[], [], [], []]
        for head in range(args.heads):   # one query head's scores at a time
            one, kv = slice(head, head + 1), slice(head // group,
                                                   head // group + 1)
            parts = plain(q[:, :, one], k[:, :, kv], v[:, :, kv],
                          w[:, :, one])
            for into, part in zip(want, parts):
                into.append(part)
        of_group = lambda parts: [sum(parts[i:i + group])
                                  for i in range(0, args.heads, group)]
        want = [want[0], want[1], of_group(want[2]), of_group(want[3])]
        return {name: round(float(
            jnp.abs(f32(a) - jnp.concatenate(b, axis=2)).max()
            / max(jnp.abs(x).max() for x in b)), 5)
            for name, a, b in zip(("out", "dq", "dk", "dv"), got, want)}

    for width in args.widths.split(","):
        d_qk, d_v = (int(n) for n in width.split("x"))
        for seq in (int(n) for n in args.lengths.split(",")):
            batch = max(1, args.tokens // seq)
            # operands as a model's projection writes them, [B, T, H x d]
            # dense, and shaped [B, T, H, d] inside the timed function: an
            # argument [B, T, H, 64] would itself be padded to the lanes
            keys = jax.random.split(jax.random.PRNGKey(seq), 4)
            q, k, v, w = (
                jax.random.normal(key, (batch, seq, n * d), jnp.bfloat16)
                for key, n, d in zip(
                    keys, (args.heads, kv_heads, kv_heads, args.heads),
                    (d_qk, d_qk, d_v, d_v)))
            shaped = lambda x, n: x.reshape(batch, seq, n, -1)
            # a tree without the rule (a parent's, under this file) has none
            results = getattr(attention, "results_in_model_arrays",
                              lambda *_: False)
            line = {"d_qk": d_qk, "d_v": d_v, "seq": seq, "batch": batch,
                    "heads": args.heads, "kv_heads": kv_heads,
                    "window": args.window, "residents": args.residents,
                    "boundary": (
                        "model_arrays" if heads_a_lane_tile(
                            seq, args.heads, kv_heads, d_qk, d_v)
                        else "model_results" if results(seq, d_qk, d_v)
                        else "xla_copies"),
                    "device": jax.devices()[0].device_kind,
                    "grid_blocks": grid_block_kinds(seq, seq, True,
                                                    window=args.window),
                    "grid_blocks_bwd": grid_block_kinds(
                        seq, seq, True, backward=True, window=args.window)}
            if args.blocks:
                line.update(
                    blocks=args.blocks, boundary=(
                        "model_results" if results(seq, d_qk, d_v)
                        else "xla_copies"),
                    grid_blocks=flash_kernels.by_block_kinds(
                        seq, args.blocks),
                    grid_blocks_bwd=flash_kernels.by_block_kinds(
                        seq, args.blocks, backward=True))
            for path in ("flash", "xla"):
                if path == "xla" and seq > 4096:
                    continue

                def loss(q, k, v, w, path=path):
                    out = causal_self_attention(
                        shaped(q, args.heads), shaped(k, kv_heads),
                        shaped(v, kv_heads), path, args.window,
                        args.blocks)
                    return (out.reshape(w.shape).astype(jnp.float32)
                            * w).sum()

                fns = {"_fwd_ms": jax.jit(loss),
                       "_fwd_bwd_ms": jax.jit(jax.grad(loss, argnums=(0, 1, 2)))}
                try:
                    for name, fn in fns.items():
                        line[path + name] = round(timed(fn, q, k, v, w), 3)
                    if path == "flash":
                        kernels = kernel_ms(fns["_fwd_bwd_ms"], q, k, v, w)
                        line.update(kernels, dq_written_bytes=dq_written_bytes(
                            fns["_fwd_bwd_ms"], q, k, v, w))
                        if kernels:
                            line["round_kernels_ms"] = round(
                                line["flash_fwd_bwd_ms"]
                                - sum(kernels.values()), 3)
                except Exception as e:  # a path that does not fit or lower
                    line[path + "_error"] = str(e)[:200]
            if args.check:
                line["against_reference"] = against_reference(
                    shaped(q, args.heads), shaped(k, kv_heads),
                    shaped(v, kv_heads))
            print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
