"""Plan-cache misses inside the window (a count; prewarm makes it 0)."""


def read(run):
    if run.spans is None:
        return None
    return run.plan_stats1["misses"] - run.plan_stats0["misses"]
