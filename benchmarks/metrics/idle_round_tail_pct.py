"""Share of the traced pass in which the chip with most idle ran nothing
while the host was behind a round's window: idle under the engine's
``round_tail`` span (cost-ledger drain, obs emission, ``log``, ``on_round``)
and under the ``between rounds`` that the harness makes up between two
round records of one block.

One of the four parts of ``device_idle_pct`` (``benchmarks/lib/idle.py``)."""

from benchmarks.lib import idle

UNIT = "%"


def read(records, trace, cell):
    return idle.group_pct(trace, "round_tail")
