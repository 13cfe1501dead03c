"""``perfbench/tests/test_families.py``'s cases, run with tier-1 (PERF.md
section 7 left this thin case for the first PR that may touch both sides):
every cell of BENCHMARK.json names a family and a reference that keep the
contract, the family's count is the state the program makes, a missing file
fails with its path, and the second family's comparison tells bfloat16 from
float32. The cases are the module's own functions, imported by path (the
benchmark's tests are no package of this suite), so each counts here under
its own name and parameters."""

import importlib.util
import os

_PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "perfbench", "tests", "test_families.py")
_spec = importlib.util.spec_from_file_location("perfbench_test_families", _PATH)
_module = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_module)

globals().update({name: case for name, case in vars(_module).items()
                  if name.startswith("test_")})
