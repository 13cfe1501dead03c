"""``perfbench/tests/test_opscopes.py``'s cases, run with tier-1 as
``tests/test_perfbench_clusterspans.py`` runs the clusterspans module's: the
reader of a trace's operation names (``perfbench/opscopes.py``) on an
``XSpace`` encoded by hand, the class of a name stack, the time by class of a
hand-made trace, the guard that keeps a reader off another run's file, the
seven ``scope_*`` readers and their entries in BENCHMARK.json. The cases are
the module's own functions, imported by path, so each counts here under its
own name."""

import importlib.util
import os

_PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "perfbench", "tests", "test_opscopes.py")
_spec = importlib.util.spec_from_file_location(
    "perfbench_test_opscopes", _PATH)
_module = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_module)

globals().update({name: case for name, case in vars(_module).items()
                  if name.startswith("test_")})
