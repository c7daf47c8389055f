"""Host planner seconds: ``WLSHIndex(...)`` plus ``export_serving_plan()``."""


def read(run):
    return run.plan_s
