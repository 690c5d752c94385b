"""Share of the device's busy time in the traced pass under no scope of
the program's table (``federated_pytorch_test_tpu/obs/scopes.py``) and
no written rule of ``benchmarks/lib/scope_tree.py``, worst chip: what a
planner cannot aim at.  None on a program without the table."""

from benchmarks.lib import scope_tree

UNIT = "%"


def read(records, trace, cell):
    return scope_tree.worst_share_pct(cell, trace,
                                      lambda tree: tree["unnamed_s"])
