"""Share of the device's busy time in the traced pass spent under the
engine's ``opt_update`` scope (Adam over the active leaves,
``apply_updates``, the write into the parameters), worst chip
(``benchmarks/lib/scope_tree.py``).  None where no op carries the
scope."""

from benchmarks.lib import scope_tree

UNIT = "%"


def read(records, trace, cell):
    sec = lambda tree: scope_tree.scope_seconds(tree, "opt_update")
    return scope_tree.worst_share_pct(cell, trace, sec,
                                      present=lambda tree: sec(tree) > 0)
