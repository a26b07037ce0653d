"""Seconds per job in ``GraphSession.layout`` (the host's
``build_layout`` of the vertex-cut tables)."""
from harness.readers import mean_seconds


def read(ctx):
    return mean_seconds(ctx, "layout")
