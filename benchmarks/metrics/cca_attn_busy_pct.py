"""Share of the device's busy time in the traced pass spent under the
program's ``cca_attn`` scope, the whole compressed-convolutional-attention
mixer: projections, the shift, convolutions and means, the unit norm and
rotary, the core (``cca_core`` lies inside it), the output projection;
forward, rematerialised and backward, worst chip
(``benchmarks/lib/zaya_work.py``)."""

from benchmarks.lib import scope_tree, zaya_work

UNIT = "%"


def read(records, trace, cell):
    return zaya_work.busy_share_pct(
        cell, trace, lambda tree: scope_tree.scope_seconds(tree, "cca_attn"))
