"""Share of the device's busy time in the traced pass spent under the
program's ``route_mlp`` scope: the router that is an MLP with a state
(the down-projection, the previous layer's state added, its norm, three
products, all float32 at the highest precision), worst chip
(``benchmarks/lib/zaya_work.py``)."""

from benchmarks.lib import scope_tree, zaya_work

UNIT = "%"


def read(records, trace, cell):
    return zaya_work.busy_share_pct(
        cell, trace, lambda tree: scope_tree.scope_seconds(tree, "route_mlp"))
