"""Share of the device's busy time in the traced pass spent under the
program's ``gdn`` scope, the whole Gated DeltaNet mixer: projections,
convolutions, the preparation of q, k, beta and g, the chunked delta rule
(``gdn_scan``), the output gate and projection; forward, rematerialised
and backward, worst chip (``benchmarks/lib/olmo_work.py``)."""

from benchmarks.lib import olmo_work, scope_tree

UNIT = "%"


def read(records, trace, cell):
    return olmo_work.busy_share_pct(
        cell, trace, lambda tree: scope_tree.scope_seconds(tree, "gdn"))
