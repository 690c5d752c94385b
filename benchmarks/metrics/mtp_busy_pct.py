"""Share of the device's busy time in the traced pass spent in ops whose
path passes through the program's ``mtp`` scope: the multi-token-
prediction layer's merge, mixer, shared expert, routing, head and loss,
whatever inner scope owns the op.  Its held experts' grouped products
carry no path and are not in it (``benchmarks/lib/glm_work.py``)."""

from benchmarks.lib import glm_work

UNIT = "%"


def read(records, trace, cell):
    return glm_work.busy_share_pct(cell, trace, lambda o: o.mtp)
