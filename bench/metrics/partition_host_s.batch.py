"""Seconds per job the partitioner spends on the host after the chip is
done: the program's ``partition.contract`` (re-contraction of the
cluster graph) and ``partition.summary`` (RF summary) spans."""
from harness.program_spans import per_job


def read(ctx):
    got = per_job(ctx, ("partition.contract", "partition.summary"))
    return None if got is None or not got[0] else got[1]
