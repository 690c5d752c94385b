"""Host milliseconds behind a round's window, which no share of
``round_seconds`` counts: 1,000 x the median of ``gap_seconds`` less
``block_switch_seconds`` (0 where the block did not change) over the
window's rounds outside the profiled pass.  That is the previous round's
tail: cost-ledger drain, obs emission, ``log``, ``on_round``.  The
median, because ``Window.pass_done`` runs inside ``on_round``: a pass's
last tail waits for the device, and the one behind the profiled pass
stops the profiler."""

import statistics

UNIT = "ms"


def read(records, trace, cell):
    tails = [r["gap_seconds"] - r.get("block_switch_seconds", 0.0)
             for r in records.rounds(traced=False) if "gap_seconds" in r]
    if not tails:
        return None
    return 1e3 * statistics.median(tails)
