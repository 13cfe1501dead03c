"""``perfbench/tests/test_qwen3_next_family.py``'s cases, run with tier-1 as
``tests/test_perfbench_lfm2.py`` runs the ``lfm2`` family's: the
``qwen3_next`` family's toy configuration rehearsed through ``run.py`` on
the CPU, its adapter's counts, the cell's entries in BENCHMARK.json (by name,
not by their place at a list's end), the levelled state and the records its
step leaves in the worker's ring, and the two readers the family brought on
canned event texts and hand-made traces. The cases are the module's own
functions, imported by path, so each counts here under its own name."""

import importlib.util
import os

_PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "perfbench", "tests", "test_qwen3_next_family.py")
_spec = importlib.util.spec_from_file_location(
    "perfbench_test_qwen3_next_family", _PATH)
_module = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_module)

globals().update({name: case for name, case in vars(_module).items()
                  if name.startswith("test_")})

# the module's case reads BENCHMARK.json without PR 69's seven
from tests.perfbench_cases import the_cell_as_its_pr_left_it  # noqa: E402

test_the_benchmark_file_gained_the_cell = the_cell_as_its_pr_left_it(_module)
