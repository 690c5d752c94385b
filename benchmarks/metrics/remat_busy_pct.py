"""Share of the device's busy time in the traced pass spent in
rematerialised forward ops (``rematted_computation`` in the path) of
every scope of the program's table, worst chip
(``benchmarks/lib/scope_tree.py``): what ``jax.checkpoint`` costs.  None
where no scope of the table is found."""

from benchmarks.lib import scope_tree

UNIT = "%"
_REMAT = scope_tree.DIRECTIONS.index("remat")


def read(records, trace, cell):
    return scope_tree.worst_share_pct(
        cell, trace,
        lambda tree: sum(n["self"][_REMAT] for n in tree["nodes"].values()),
        present=lambda tree: bool(tree["nodes"]))
