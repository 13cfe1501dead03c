"""``perfbench/tests/test_nemotron_h_family.py``'s cases, run with tier-1 as
``tests/test_perfbench_lfm2.py`` runs the ``lfm2`` family's: the
``nemotron_h`` family's toy configuration rehearsed through ``run.py`` on
the CPU, its adapter's counts, the cell's entries in BENCHMARK.json (by name,
not by their place at a list's end), the levelled state and the records its
step leaves in the worker's ring, and the four readers the PR brought on
canned event texts and hand-made traces. The cases are the module's own
functions, imported by path, so each counts here under its own name."""

import importlib.util
import os

_PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "perfbench", "tests", "test_nemotron_h_family.py")
_spec = importlib.util.spec_from_file_location(
    "perfbench_test_nemotron_h_family", _PATH)
_module = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_module)

globals().update({name: case for name, case in vars(_module).items()
                  if name.startswith("test_")})


def test_the_benchmark_file_gained_the_cell(monkeypatch):
    """The module's case counts ``workloads`` as PR 58 left the list (ten
    cells); entries are only ever appended, and PR 62 appended one. Here the
    case reads the list up to and including its own cell: held by name, every
    other assertion as the module has it (the module's file is the
    benchmark's, a ``benchmark`` PR's to re-anchor), as
    ``tests/test_perfbench_clusterspans.py`` does for its module's."""
    import json

    from tests.perfbench_cases import strike_later_metrics

    strike_later_metrics(monkeypatch)   # PR 69's seven list every step cell
    load = json.load

    def up_to_the_cell(f):
        bench = load(f)
        if "workloads" in bench:
            names = [w["name"] for w in bench["workloads"]]
            bench["workloads"] = bench["workloads"][
                :names.index(_module.CELL) + 1]
        return bench

    monkeypatch.setattr(json, "load", up_to_the_cell)
    _module.test_the_benchmark_file_gained_the_cell()
