"""hbm_plan_gib: what the compiler plans for the step that runs, per chip:
temporaries + arguments + outputs - aliased (compiled.memory_analysis())."""


def read(r):
    return r.plan_bytes / 2**30 if r.plan_bytes else None
