"""Worst marginal error of the hyper-connections' mixing matrices (the
largest ``|row sum - 1|`` or ``|column sum - 1|`` of any ``H_res`` of a
step), averaged over the window's local steps (the engine's round field
of the same name)."""

UNIT = "ratio"


def read(records, trace, cell):
    vals = [r["mhc_marginal_err"] for r in records.rounds()
            if "mhc_marginal_err" in r]
    return sum(vals) / len(vals) if vals else None
