"""Seconds per job in ``GraphSession.partition`` (clustering, game,
transform and the host's summary of the result), a host span that ends
once the assignment is on the host."""
from harness.readers import mean_seconds


def read(ctx):
    return mean_seconds(ctx, "partition")
