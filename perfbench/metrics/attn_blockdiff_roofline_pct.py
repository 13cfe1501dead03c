"""attn_blockdiff_roofline_pct: the matmul operations that the
block-diffusion mask leaves each flash-attention call under it, over the
time those calls took x the chip's published bf16 peak
(perfbench/peaks.json), chip 0, over every such call of the traced steps.
The kernels are bound by the MXU (their bytes are O(T d) against O(T^2 d)
operations), so the peak is their roofline.

The calls are found as ``attn_blockdiff_ms`` finds them. What a call needs
is counted from its own instruction (``needed_flops``): the query heads B
and the positions T from q's operand [B, T, d_qk] (keys and values may have
fewer heads: each query head still meets its own pairs), the values' width
d_v from the third operand, and the block length D from the call's name
(``flash_fwd_bd<D>``). The T positions are two streams of L = T / 2, a
noisy and a clean copy of one sequence in blocks of D: a noisy query sees
the D noisy keys of its own block and the clean keys of the blocks before
it, a clean query the clean keys of its own block and of those before it
(``live_pairs``: ``L D + L (L - D) / 2 + L (L + D) / 2 = L^2 + L D`` a
head, of the square's ``4 L^2`` and of a causal mask's ``L (2 L + 1)``). A
pair costs the forward kernel 2 d_qk (q . k) + 2 d_v (p v) operations and
the backward kernel 2 (3 d_qk + 2 d_v), as ``attn_kernel_roofline_pct``
counts them. Work a kernel does beyond that (all of a diagonal tile but
its blocks) is not needed and lowers the share.

None where the traced steps hold no such call or the device's peak is
unknown.
"""

from perfbench import xplane
from perfbench.metrics.attn_blockdiff_ms import BY_BLOCK
from perfbench.metrics.attn_kernel_roofline_pct import _OPERANDS, _SHAPE


def live_pairs(length: int, block: int) -> int:
    """Query-key pairs a head that the mask leaves of two streams of
    ``length`` positions in blocks of ``block``."""
    return (length * block                      # noisy on its own block
            + length * (length - block) // 2    # noisy on the clean before
            + length * (length + block) // 2)   # clean on clean, own block in


def needed_flops(event_text: str):
    """Operations one kernel call under the mask needs, from its HLO text;
    None for a text that is no such call's or whose operands cannot be
    read."""
    kind = BY_BLOCK.match(event_text)
    operands = _OPERANDS.search(event_text)
    if not kind or not operands:
        return None
    shapes = [tuple(int(n) for n in dims.split(","))
              for dims in _SHAPE.findall(operands.group(1))]
    if len(shapes) < 3 or any(len(s) != 3 for s in shapes[:3]):
        return None
    (b, t, d_qk), _, third = shapes[:3]
    # forward: V^T [B_kv, d_v, T]; backward: V [B_kv, T, d_v]
    forward = kind.group(1) == "fwd"
    d_v = third[1] if forward else third[2]
    pairs = b * live_pairs(t // 2, int(kind.group(2)))
    per_pair = (2 * (d_qk + d_v) if forward else 2 * (3 * d_qk + 2 * d_v))
    return pairs * per_pair


def read(r):
    if not (r.trace and r.trace.ops and r.peaks):
        return None
    needed, spent = 0, 0
    for _, _, _, ops in xplane.step_device_work(r.trace, 0):
        for name, start, end in ops:
            flops = needed_flops(name)
            if flops:
                needed, spent = needed + flops, spent + (end - start)
    if not spent:
        return None
    return 100.0 * needed / (spent / 1e9 * r.peaks["bf16_flops_per_s"])
