"""Runs of the partitioner's jitted body per job: the program's
``partition.attempt`` spans (a run that overflows its static caps is
run again with larger ones)."""
from harness.program_spans import per_job


def read(ctx):
    got = per_job(ctx, ("partition.attempt",))
    return None if got is None or not got[0] else got[0]
