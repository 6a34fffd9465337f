"""Device-to-device bytes per job: the program's ``PlanStats.d2d_bytes``
counter across the traced window, over the jobs completed in it."""


def read(rec):
    t = rec.traced
    if rec.kind != "offload" or t is None or not t.jobs:
        return None
    return t.counters["d2d_bytes"] / len(t.jobs)
