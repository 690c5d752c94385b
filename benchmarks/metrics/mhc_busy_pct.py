"""Share of the device's busy time in the traced pass spent in ops whose
path passes through the program's ``mhc`` scope: the hyper-connections'
maps (norm, projections, sigmoids, Sinkhorn) and mixing (contraction,
expansion), forward, rematerialised and backward
(``benchmarks/lib/xing_work.py``)."""

from benchmarks.lib import xing_work

UNIT = "%"


def read(records, trace, cell):
    return xing_work.busy_share_pct(cell, trace, lambda o: o.mhc)
