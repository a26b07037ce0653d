"""Seconds per batch job: the window's time up to the end of its last
job, over the jobs it completed."""


def read(ctx):
    jobs = ctx.results.get("jobs")
    if not jobs:
        return None
    t0, t1 = ctx.results["window"]
    return (t1 - t0) / len(jobs)
