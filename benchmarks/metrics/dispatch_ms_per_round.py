"""Host milliseconds per round inside the engine's instrumented jitted
calls (enqueueing the round's programs, and the block switch's where one
came before it): 1,000 x the mean of ``dispatch_seconds`` over the
window's rounds outside the profiled pass.  The window compiles nothing,
so no compile is in it."""

UNIT = "ms"


def read(records, trace, cell):
    rounds = [r for r in records.rounds(traced=False)
              if "dispatch_seconds" in r]
    if not rounds:
        return None
    return 1e3 * sum(r["dispatch_seconds"] for r in rounds) / len(rounds)
