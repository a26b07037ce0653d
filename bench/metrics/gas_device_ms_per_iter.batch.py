"""Device milliseconds per GAS iteration: the device's busy time inside
the traced job's ``gas.run`` spans (dispatch until the values are on the
host) over that job's PageRank and WCC iterations."""
from harness.program_spans import busy_under


def read(ctx):
    jobs = ctx.results.get("jobs")
    busy = busy_under(ctx, "gas.run")
    if busy is None or not jobs:
        return None
    its = int(jobs[0]["pagerank_iters"]) + int(jobs[0]["cc_iters"])
    return 1e3 * busy / its if its else None
