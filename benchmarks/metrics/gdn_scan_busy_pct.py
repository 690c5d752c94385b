"""Share of the device's busy time in the traced pass spent in ops of the
program's ``gdn_scan`` scope (``benchmarks/lib/scopes.py``)."""

from benchmarks.lib import scopes

UNIT = "%"


def read(records, trace, cell):
    return scopes.busy_share_pct(cell, trace, "gdn_scan")
