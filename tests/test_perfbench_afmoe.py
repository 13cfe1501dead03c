"""``perfbench/tests/test_afmoe_family.py``'s cases, run with tier-1 as
``tests/test_perfbench_families.py`` runs the families': the ``afmoe``
family's toy configuration rehearsed through ``run.py`` on the CPU, the
cell's entries in BENCHMARK.json, and the two readers the family brought on
hand-made traces and on the trace recorded on the chip. The cases are the
module's own functions, imported by path, so each counts here under its own
name."""

import importlib.util
import os

_PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "perfbench", "tests", "test_afmoe_family.py")
_spec = importlib.util.spec_from_file_location("perfbench_test_afmoe_family",
                                               _PATH)
_module = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_module)

globals().update({name: case for name, case in vars(_module).items()
                  if name.startswith("test_") or name == "recorded"})


def test_the_benchmark_file_gained_the_cell_and_nothing_else_moved():
    """The module's case of this name holds PR 44's entries to the END of
    BENCHMARK.json's lists, where they stood until a later PR appended its
    own (PR 48: a configuration, a cell, two metrics). Entries are only
    ever appended, so the same facts are held here by name and by order:
    what PR 44 added is there, unchanged, and after everything older."""
    import json

    from tests.perfbench_cases import without_later_metrics

    with open(os.path.join(os.path.dirname(_PATH), "..", "..",
                           "BENCHMARK.json")) as f:
        # PR 69's seven list every step cell
        bench = without_later_metrics(json.load(f))
    cell = "trinity-mini.step-16k"
    cells = [w["name"] for w in bench["workloads"]]
    assert cells.index(cell) == 5 and bench["workloads"][5]["chips"] == 1
    assert [c["name"] for c in bench["configs"]].index("trinity-mini") == 3
    joined = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
              if cell in m.get("workloads", ())}
    assert joined == {
        "tokens_per_s_per_chip", "host_gap_ms", "device_step_ms", "mfu_pct",
        "device_idle_pct", "hbm_plan_gib", "report_ms", "attn_kernel_ms",
        "loss_head_ms", "compile_s", "step_trace_lower_s", "step_backend_s",
        "step_cache_hit_pct", "attn_window_ms", "attn_masked_roofline_pct"}
    older = set(cells[:5])
    for m in bench["per_layer"] + bench["end_to_end"]:
        lists = m.get("workloads", ())
        if cell in lists:   # after every older cell, before any later one
            assert set(lists[:lists.index(cell)]) <= older
            assert not older & set(lists[lists.index(cell) + 1:])
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index("attn_window_ms")
    assert names[at:at + 2] == ["attn_window_ms", "attn_masked_roofline_pct"]
    assert at == 35     # PR 44 appended them to the 35 that were there
    for m in bench["per_layer"][at:at + 2]:
        assert m["workloads"][0] == cell and m["layer"] == "kernel"
        assert m["moves"] == "tokens_per_s_per_chip"
        assert m["source"] == "device_trace"
