"""The slowest single dispatch of the window: 1,000 x the largest
``dispatch_max_seconds`` (``obs/costs.py``: host seconds inside one
instrumented jitted call) over the window's rounds outside the profiled
pass.  Beside ``dispatch_ms_per_round``'s mean it shows the one call
that left jax's fast path.  None on a program without the field."""

UNIT = "ms"


def read(records, trace, cell):
    found = [r["dispatch_max_seconds"] for r in records.rounds(traced=False)
             if "dispatch_max_seconds" in r]
    return 1e3 * max(found) if found else None
