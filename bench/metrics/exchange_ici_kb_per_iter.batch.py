"""Kilobytes one chip sends over the interconnect per GAS iteration, both
mirror-sync phases, as padded on the wire: the ``ici_bytes`` the mesh
GAS engine records on its ``gas.run`` spans (static, from the layout's
halo tables), averaged over the window's program runs."""
from harness.program_spans import records


def read(ctx):
    recs = records(ctx)
    if recs is None:
        return None
    got = [r[4]["ici_bytes"] for r in recs
           if r[0] == "gas.run" and "ici_bytes" in r[4]]
    return 1e-3 * sum(got) / len(got) if got else None
