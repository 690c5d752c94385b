"""Share of round time in the engine's ``sync`` segment:
sum of ``sync_seconds`` over sum of ``round_seconds``.  Read in the
traced run only: the segments are execution times only while the obs
recorder is on (``RoundKernel._obs_sync`` blocks at each boundary)."""

UNIT = "%"


def read(records, trace, cell):
    return records.share_pct("sync_seconds")
