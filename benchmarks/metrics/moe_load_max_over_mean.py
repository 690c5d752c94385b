"""Most loaded held expert over the mean load of the held experts, worst
layer, averaged over the window's local steps (the engine's round field
of the same name)."""

UNIT = "ratio"


def read(records, trace, cell):
    vals = [r["moe_load_max_over_mean"] for r in records.rounds()
            if "moe_load_max_over_mean" in r]
    return sum(vals) / len(vals) if vals else None
