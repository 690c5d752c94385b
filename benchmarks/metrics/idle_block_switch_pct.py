"""Share of the traced pass in which the chip with most idle ran nothing
while the host switched blocks: idle under the engine's ``block_switch``
span and its parts, and under the ``block switch`` that the harness makes
up between the round records of two blocks (all of it on a program that
does not stamp the switch).

One of the four parts of ``device_idle_pct`` (``benchmarks/lib/idle.py``)."""

from benchmarks.lib import idle

UNIT = "%"


def read(records, trace, cell):
    return idle.group_pct(trace, "block_switch")
