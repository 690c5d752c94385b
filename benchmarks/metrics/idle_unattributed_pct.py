"""Share of the traced pass in which the chip with most idle ran nothing
and no host span lay over it: a hole in the program's timeline.

One of the four parts of ``device_idle_pct`` (``benchmarks/lib/idle.py``)."""

from benchmarks.lib import idle

UNIT = "%"


def read(records, trace, cell):
    return idle.group_pct(trace, "unattributed")
