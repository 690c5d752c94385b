"""The delta rule's share of its roofline in the traced pass: the least
time the chip could take for the recurrences' products and one pass over
their operands (``lm_work.gdn_scan_work``) over the device time of the
``gdn_scan`` scope."""

from benchmarks.lib import lm_work, scopes

UNIT = "%"


def read(records, trace, cell):
    return scopes.roofline_pct(
        cell, trace, records, "gdn_scan",
        lambda cfg, r: lm_work.gdn_scan_work(cfg, r["block"], r["tokens"]))
