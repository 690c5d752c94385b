"""Peak of what the runtime reserved on the fullest chip for the
programs' scratch (``peak_bytes_reserved``), in GiB.  Not part of
``peak_hbm_gib``: the two peaks need not coincide, and much of a
reservation is tile padding (PERF.md, findings of PR 22)."""

UNIT = "GiB"


def read(records, trace, cell):
    reserved = records.counters.get("scratch_reserved_bytes")
    return None if not reserved else reserved / 2**30
