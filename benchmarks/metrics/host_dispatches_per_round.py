"""Train-phase host dispatches per round, from the round records (exact)."""

UNIT = "count"


def read(records, trace, cell):
    rounds = [r for r in records.rounds() if "host_dispatches" in r]
    if not rounds:
        return None
    return sum(r["host_dispatches"] for r in rounds) / len(rounds)
