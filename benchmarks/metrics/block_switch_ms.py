"""Host milliseconds of one block switch: 1,000 x the median of
``block_switch_seconds`` (the engine's ``block_switch`` span, on the first
round of each block visit) over the window's rounds outside the profiled
pass.  The median, because a pass's first switch follows the harness's
own work in ``on_round``."""

import statistics

UNIT = "ms"


def read(records, trace, cell):
    switches = [r["block_switch_seconds"]
                for r in records.rounds(traced=False)
                if "block_switch_seconds" in r]
    if not switches:
        return None
    return 1e3 * statistics.median(switches)
