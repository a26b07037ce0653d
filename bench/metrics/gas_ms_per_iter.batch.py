"""Milliseconds per GAS iteration: the window's PageRank and WCC spans
(each ends with the values on the host) over the iterations they ran."""
from harness.readers import gas_iterations, spans


def read(ctx):
    its = gas_iterations(ctx)
    got = spans(ctx, "pagerank") + spans(ctx, "cc")
    if not its or not got:
        return None
    return 1e3 * sum(s[2] - s[1] for s in got) / its
