"""Device milliseconds of the mirror exchange per GAS iteration: the
time the collective ops run inside the traced job's ``gas.run`` spans
(dispatch until the values are on the host), averaged over the traced
devices, over that job's PageRank and WCC iterations.  Where the
exchange overlaps no compute this is its exposed time."""
from harness import trace
from harness.program_spans import busy_in, on_trace_clock


def read(ctx):
    jobs = ctx.results.get("jobs")
    spans = on_trace_clock(ctx)
    if spans is None or not jobs or not ctx.trace["devices"]:
        return None
    runs = [(a, b) for n, a, b, _ in spans if n == "gas.run"]
    its = int(jobs[0]["pagerank_iters"]) + int(jobs[0]["cc_iters"])
    if not runs or not its:
        return None
    t0, t1 = ctx.trace_window
    devs = ctx.trace["devices"]
    ns = 0.0
    for ev in devs.values():
        union = trace.busy_union(
            [e for e in ev if trace.is_collective(e[2])], t0, t1)
        ns += sum(busy_in(union, a, b) for a, b in runs)
    return 1e-6 * ns / len(devs) / its
