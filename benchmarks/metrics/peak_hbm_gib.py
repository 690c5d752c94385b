"""Peak of live buffers after the window (``peak_bytes_in_use``),
fullest chip, in GiB: the result line's ``memory_peak_bytes``."""

UNIT = "GiB"


def read(records, trace, cell):
    peak = records.counters.get("peak_hbm_bytes")
    return None if not peak else peak / 2**30
