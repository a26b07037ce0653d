"""Share of the traced window's device idle time, in %, that no span of
the program covers: the host work the program's spans do not explain."""
from harness.program_spans import idle_split


def read(ctx):
    split = idle_split(ctx)
    if split is None:
        return None
    total = sum(split.values())
    return 100.0 * split.get("none", 0.0) / total if total > 0 else None
