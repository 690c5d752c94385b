"""Share of device busy time in convolution ops and convolution fusions
(the profiler's ``hlo_category``; on a TPU a matrix product is one too):
the MXU's part of what the chip does.  Averaged over the chips."""

from benchmarks.lib import xplane

UNIT = "%"


def read(records, trace, cell):
    if trace is None:
        return None
    t0, t1 = trace.window
    shares = []
    for ops in trace.devices.values():
        busy = xplane.busy_ns(xplane.leaf_ops(ops), t0, t1)
        if busy <= 0:
            return None
        shares.append(xplane.category_ns(ops, xplane.is_convolution, t0, t1)
                      / busy)
    return 100.0 * sum(shares) / len(shares)
