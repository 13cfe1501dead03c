"""``perfbench/tests/test_sdar_family.py``'s cases, run with tier-1 as
``tests/test_perfbench_lfm2.py`` runs the ``lfm2`` family's: the ``sdar``
family's toy configuration rehearsed through ``run.py`` on the CPU, traced
and untraced, its adapter's counts against ISSUE 65's, the cell's entries in
BENCHMARK.json (by name, not by their place at a list's end), and the two
readers the family brought on canned event texts and hand-made traces. The
cases are the module's own functions, imported by path, so each counts here
under its own name."""

import importlib.util
import os

_PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "perfbench", "tests", "test_sdar_family.py")
_spec = importlib.util.spec_from_file_location(
    "perfbench_test_sdar_family", _PATH)
_module = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_module)

globals().update({name: case for name, case in vars(_module).items()
                  if name.startswith("test_")})


def test_the_benchmark_file_gained_the_cell(monkeypatch):
    """The module's case holds the family's two metrics to the END of
    ``per_layer`` as PR 65 left it; entries are only ever appended, and PR
    67's cell brought five more. Here the case reads the file as the cell
    found it, as ``tests/test_perfbench_mellum.py`` does for its own: the
    names of the cells that came after struck from every metric's
    ``workloads``, and a metric that then lists no cell (one a later cell
    brought) left out; every assertion as the module has it (the module's
    file is the benchmark's, a ``benchmark`` PR's to re-anchor)."""
    import json

    from tests.perfbench_cases import strike_later_metrics

    strike_later_metrics(monkeypatch)   # PR 69's seven list every step cell
    load = json.load

    def as_the_cell_found_it(f):
        bench = load(f)
        if "workloads" not in bench:      # a configuration, a traffic file
            return bench
        names = [w["name"] for w in bench["workloads"]]
        later = set(names[names.index(_module.CELL) + 1:])
        for metric in bench["per_layer"] + bench["end_to_end"]:
            if "workloads" in metric:
                metric["workloads"] = [name for name in metric["workloads"]
                                       if name not in later]
        bench["per_layer"] = [m for m in bench["per_layer"]
                              if m.get("workloads") != []]
        return bench

    monkeypatch.setattr(json, "load", as_the_cell_found_it)
    _module.test_the_benchmark_file_gained_the_cell()
