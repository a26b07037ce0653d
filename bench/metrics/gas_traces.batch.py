"""Traces of the mesh GAS loop inside the window: the ``traced`` counts
the mesh GAS engine records on its ``gas.run`` spans.  Set-up runs one
whole job of the same shapes, so this should read 0."""
from harness.program_spans import records


def read(ctx):
    recs = records(ctx)
    if recs is None:
        return None
    got = [r[4]["traced"] for r in recs
           if r[0] == "gas.run" and "traced" in r[4]]
    return sum(got) if got else None
