"""Replication factor of the window's partitions (mean over jobs), as
the partitioner's ``stats`` give it; every job's value is checked against
the plain reference before it is reported.  It sets the exchange bytes of
every GAS iteration."""


def read(ctx):
    jobs = ctx.results.get("jobs")
    if not jobs:
        return None
    return sum(j["stats"]["rf"] for j in jobs) / len(jobs)
