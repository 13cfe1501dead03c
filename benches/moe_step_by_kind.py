"""The routed-expert path of a step cell's kept trace, by kind of
operation: what ``moe_ms`` counts (``perfbench/metrics/moe_ms.py``: an
operation with an array of the tokens x k row buffer, of (tokens, k) or of
the router's (tokens, experts); in a cell the reader does not list, the
first two), split the way ``PERF.md`` section 5 splits it. Runs anywhere (it reads a file); the numbers are the chip's.

    python3 perfbench/run.py --workload lfm2-8b-a1b.step-8k --seed 1 \\
        --seconds 32 --trace 1 --keep-trace chiprun_out/pr53/lfm2.xplane.pb
    python benches/moe_step_by_kind.py lfm2-8b-a1b.step-8k \\
        chiprun_out/pr53/lfm2.xplane.pb

Prints one JSON line: ms a step (chip 0, mean over the traced steps) and
operations a step of each kind, their sum beside the union that ``moe_ms``
reads, and the largest operations of the kinds that are no matmul. An
operation inside one of the layer's loops carries a chunk's shapes
([4096, ...]) and none of the marks, as ``moe_ms`` says of itself: the
loops' bodies are not in this account.
"""

import collections
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def kind_of(name: str, short: str, tokens: int, k: int, d: int) -> str:
    """``name`` is an operation's HLO text, ``short`` its name and result."""
    op, pairs = short.split(" ")[0], tokens * k
    widths = "|".join(str(d // parts) for parts in (1, 2, 4))
    result = name.split("(")[0]
    # XLA's own kernel, or since PR 61 ``ops/grouped_matmul.py``'s
    if op.startswith(("ragged-dot", "grouped_matmul")):
        return "grouped matmuls"
    if op.startswith("to_tokens"):
        return "to_tokens kernel"
    if op.startswith("unwritten"):
        return "unwritten"
    if "dynamic-update-slice" in op or "dynamic_update_slice" in op:
        return "loops' writes"
    # the gathers back: [tokens, k, a block of the rows' columns] formed or
    # summed, a gather whose result is tokens x k rows of such a block, the
    # cut of its source, the join of the blocks' sums
    if re.search(rf"\[{tokens},{k},(?:{widths})\]", name) or (
            re.search(rf"bf16\[\d+,(?:{widths})\]", result)
            and re.search(rf"bf16\[{pairs},{d}\]", name.split("(", 1)[-1])):
        return "gathers back to the tokens and their sums"
    if re.search(rf"bf16\[{pairs},(?:{widths})\]", result) and (
            re.search(rf"s32\[{pairs}", name.split("(", 1)[-1])):
        return "gathers back to the tokens and their sums"
    if re.search(rf"f32\[{pairs}\]", result):
        return "scalar gathers over tokens x k"
    if op.startswith("sort") or re.search(rf"[su]32\[{pairs}[\],]", name):
        return "sort, counts and places"
    if re.search(rf"\[{pairs}(?:,\d+)?\]", name):
        return "other passes over tokens x k rows"
    return "router and (tokens, k) arrays"


def main():
    from perfbench import run, xplane
    from perfbench.metrics import moe_ms

    cell, path = sys.argv[1:3]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        loaded = run.load_cell(json.load(f), cell)
    model, traffic = loaded["model"], loaded["traffic"]
    tokens, k = traffic["batch"] * traffic["seq"], model["num_experts_per_tok"]
    # a cell the reader does not list (the window cell: its rule "(tokens,
    # the router's width)" takes attention's arrays) is read by the reader's
    # first two rules alone, and misses the router's scores
    routed = moe_ms.pattern(model, traffic) or re.compile(
        r"\b[a-z]\w*\[(?:(?:\d+,)*%d(?:,\d+)*|(?:\d+,)*%d,%d(?:,\d+)*)\]"
        % (tokens * k, tokens, k))
    steps = xplane.step_device_work(xplane.load(path), 0)
    ms, count, largest = (collections.Counter() for _ in range(3))
    union, whole = 0.0, 0.0
    for _, _, _, ops in steps:
        mine = []
        for name, start, end in ops:
            short = xplane.short_name(name)
            if short.startswith(xplane._CONTROL_FLOW):
                continue
            whole += end - start
            if not routed.search(name):
                continue
            kind = kind_of(name, short, tokens, k, model["hidden_size"])
            ms[kind] += end - start
            count[kind] += 1
            if kind not in ("grouped matmuls", "to_tokens kernel"):
                largest[kind, re.sub(r"[.\d]+ ", " ", short)] += end - start
            mine.append((start, end))
        union += xplane.length(xplane.union(mine))
    n = len(steps) * 1e6
    print(json.dumps({
        "cell": cell, "steps": len(steps),
        "ms_a_step": {kind: round(t / n, 3) for kind, t in ms.most_common()},
        "ops_a_step": {kind: round(c / len(steps), 1)
                       for kind, c in count.items()},
        "sum_ms": round(sum(ms.values()) / n, 3),
        "moe_ms_union": round(union / n, 3),
        "all_operations_ms": round(whole / n, 3),
        "largest_ms_a_step": [[kind, op, round(t / n, 3)] for (kind, op), t
                              in largest.most_common(16)]}))


if __name__ == "__main__":
    main()
