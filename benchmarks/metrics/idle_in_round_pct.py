"""Share of the traced pass in which the chip with most idle ran nothing
inside a round's own window: idle under ``stage``, ``train``, ``comm``,
``sync``, ``overlap`` and ``overlap_dispatch``.

One of the four parts of ``device_idle_pct`` (``benchmarks/lib/idle.py``)."""

from benchmarks.lib import idle

UNIT = "%"


def read(records, trace, cell):
    return idle.group_pct(trace, "in_round")
