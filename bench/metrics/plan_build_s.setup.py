"""Seconds the set-up spent building partition plans: the plan cache's
``build_s`` (the ``gcn.plan.build`` spans; disk reloads excluded) as the
window opens."""
UNIT = "s"
MOVES = "setup_s"


def read(run):
    value = run.stats0.get("cache_build_s")
    return None if value is None else float(value)
