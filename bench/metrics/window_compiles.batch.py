"""Compilations inside the window: the program's compile records there
less those the persistent cache answered.  Set-up warms every shape, so
this should read 0."""
from harness.program_spans import compilations, records


def read(ctx):
    recs = records(ctx)
    return None if recs is None else compilations(recs)
