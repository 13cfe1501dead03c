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
