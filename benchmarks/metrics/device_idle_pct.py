"""Share of the traced pass in which no op ran on a chip, worst chip."""

from benchmarks.lib import xplane

UNIT = "%"


def read(records, trace, cell):
    if trace is None:
        return None
    t0, t1 = trace.window
    return 100.0 * max(
        1.0 - xplane.busy_ns(xplane.leaf_ops(ops), t0, t1) / (t1 - t0)
        for ops in trace.devices.values())
