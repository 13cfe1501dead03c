"""scope_unnamed_pct: the share of one step's device busy time
(``device_step_ms``) inside operations that no class holds: those whose name
stack has no ``rt.`` segment (``unnamed``: the program wrote them outside
every ``steptrace.device_scope``) and those with no name stack of the
program's (``no_path``: copies and the like that the compiler made, unnamed or
named after an argument), percent, chip 0.
What the other six ``scope_*_ms`` cannot see. None where no operation of the
step carries a class (a parent without the scopes), and where the trace's
file cannot be proved to be this run's."""

from perfbench import opscopes, xplane


def read(r):
    classes = opscopes.read_classes(r.trace)
    busy = xplane.device_step_ms(r.trace) if classes else None
    if not busy:
        return None
    return 100.0 * (classes[opscopes.UNNAMED] + classes[opscopes.NO_PATH]) / busy
