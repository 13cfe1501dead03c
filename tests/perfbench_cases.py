"""What the tier-1 wrappers of the benchmark's own family tests
(``tests/test_perfbench_<family>.py``) share. A family's module under
``perfbench/tests/`` holds BENCHMARK.json as its PR left it, and entries are
only ever appended: a wrapper shows its module's case the file as the case
found it and keeps every assertion as the module has it (the module's file
is the benchmark's, a ``benchmark`` PR's to re-anchor)."""

import json

# per-layer metrics that list EVERY step cell and came after the families'
# cases were written: PR 69's seven readers of the device's time by class
LATER_METRICS = ("scope_mixer_ms", "scope_experts_ms", "scope_mlp_ms",
                 "scope_norm_ms", "scope_vocab_ms", "scope_optimizer_ms",
                 "scope_unnamed_pct")


def without_later_metrics(bench):
    """A loaded BENCHMARK.json without ``LATER_METRICS``; any other file
    (a configuration, a traffic file) as it is."""
    if isinstance(bench, dict) and "per_layer" in bench:
        bench["per_layer"] = [m for m in bench["per_layer"]
                              if m["name"] not in LATER_METRICS]
    return bench


def strike_later_metrics(monkeypatch):
    """From here to the test's end ``json.load`` hands out BENCHMARK.json
    without ``LATER_METRICS``. A wrapper that patches ``json.load`` itself
    calls this first: its own patch then reads through this one."""
    load = json.load
    monkeypatch.setattr(
        json, "load", lambda f, **kw: without_later_metrics(load(f, **kw)))


def the_cell_as_its_pr_left_it(module):
    """A wrapper's ``test_the_benchmark_file_gained_the_cell``: the module's
    case holds the SET of metrics that list its cell as its PR left
    BENCHMARK.json, so here it reads the file without ``LATER_METRICS``,
    every assertion as the module has it."""
    def test_the_benchmark_file_gained_the_cell(monkeypatch):
        strike_later_metrics(monkeypatch)
        module.test_the_benchmark_file_gained_the_cell()

    return test_the_benchmark_file_gained_the_cell
