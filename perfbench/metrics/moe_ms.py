"""moe_ms: device time of one step inside the routed-expert path
(``ray_tpu/ops/moe.py``: ``topk_routing``, ``held_expert_ffn``), forward,
recomputed forward and backward, chip 0, median over the traced steps:
the router's matmul and top-k, the sort of the token-expert pairs, the
gathers that bring a pair its token's row and take it back, the grouped
matmuls and the weighted sum over a token's pairs.

How the operations are found: an ``XLA Ops`` event carries its instruction's
HLO text with the shapes of its operands and result, and an operation
counts when an array in it has

- a dimension equal to the step's token-expert pairs, tokens x
  ``num_experts_per_tok`` (the row buffer: here 16,384 x 8 = 131,072), or
- two neighbouring dimensions (tokens, ``num_experts_per_tok``): the same
  buffer seen a token at a time, or
- two neighbouring dimensions (tokens, ``n_routed_experts_published``),
  here (16,384, 256): the router's scores, their top-k and the matmul that
  forms the router's gradient from the scores',

and is no ``while``, ``conditional`` or ``call`` (their time is that of the
operations inside them). The grouped matmuls (``ragged-dot-*``: the TPU
compiler's own kernel for ``jax.lax.ragged_dot``) carry the row buffer and
are found by the first rule.

The router's width alone is no mark: 256 is also a head's keys-and-values
width (``qk_nope_head_dim + v_head_dim`` = 128 + 128), and attention's
backward joins ``dk_nope | dv`` into ``bf16[2,32,1024,8,256]`` (6 fusions and
their copies a step, about 270 MB written each): attention's, not counted.

What it would mistake: any other operation with an array of one of those
shapes counts (in the configuration it was written for none has: 131,072
equals no other size of the program, and no other array has 16,384 beside 8
or beside 256); an operation of the path that carries none of them is
missed (the small ``ragged-dot-metadata`` calls, the counts of tokens an
expert, the router's share of the optimizer's update); the shared expert
and the layer's norm are not routed and rightly not counted; the experts'
optimizer update is not counted (it has the held experts' dimension alone).
None without a traced device or where no traced step holds such an
operation (a family without the path)."""

import re
import statistics

from perfbench import xplane


def pattern(model: dict, traffic: dict):
    """The expression an operation's text is searched with, or None where
    the configuration has no routed experts."""
    k = model.get("num_experts_per_tok")
    width = model.get("n_routed_experts_published")
    if not k or not width:
        return None
    tokens = traffic["batch"] * traffic["seq"]
    return re.compile(
        r"\b[a-z]\w*\[(?:(?:\d+,)*%d(?:,\d+)*|(?:\d+,)*%d,(?:%d|%d)(?:,\d+)*)\]"
        % (tokens * k, tokens, k, width))


def read(r):
    if not (r.trace and r.trace.ops):
        return None
    routed = pattern(r.model, r.traffic)
    if routed is None:
        return None
    per_step = []
    for _, _, _, ops in xplane.step_device_work(r.trace, 0):
        mine = [(s, e) for name, s, e in ops if routed.search(name)
                and not xplane.short_name(name).startswith(
                    xplane._CONTROL_FLOW)]
        per_step.append(xplane.length(xplane.union(mine)))
    per_step = [ns for ns in per_step if ns]
    return statistics.median(per_step) / 1e6 if per_step else None
