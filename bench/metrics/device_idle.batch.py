"""Share of the traced window in which no op ran on the device, in %
(busy time averaged over the cell's chips)."""
from harness.readers import device_idle_pct


def read(ctx):
    return device_idle_pct(ctx)
