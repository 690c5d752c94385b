"""Share of round time in local training: ``train_seconds`` (classifier
engine) or ``compute_seconds`` (CPC: train and exchange are one program)
over ``round_seconds``.  Traced run only, see ``stage_pct``."""

UNIT = "%"


def read(records, trace, cell):
    share = records.share_pct("train_seconds")
    return share if share is not None else records.share_pct("compute_seconds")
