"""Bytes the exchange puts on the wire per round, from the records'
``bytes_on_wire`` (a count from shapes, exact), in MB (1e6 bytes)."""

UNIT = "MB/round"


def read(records, trace, cell):
    rounds = [r for r in records.rounds() if "bytes_on_wire" in r]
    if not rounds:
        return None
    return sum(r["bytes_on_wire"] for r in rounds) / len(rounds) / 1e6
