"""Share of the traced pass in which a collective ran on a chip and no
other op did, worst chip.  0 where the traced pass holds no collective
op, as on one chip."""

from benchmarks.lib import xplane

UNIT = "%"


def read(records, trace, cell):
    if trace is None:
        return None
    t0, t1 = trace.window
    return 100.0 * max(xplane.collective_exposed_ns(ops, t0, t1)
                       for ops in trace.devices.values()) / (t1 - t0)
