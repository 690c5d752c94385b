"""The delta rule's share of its roofline at 30 heads of 96-wide keys and
192-wide values in the traced pass: the least time the chip could take
for the recurrences' products and one pass over their operands, unpadded
(``olmo_work.gdn_scan_work``: forward in every Gated DeltaNet layer,
backward where the gradient reaches; the larger of operations / peak and
bytes / bandwidth) over the device time of the ``gdn_scan`` scope."""

from benchmarks.lib import olmo_work

UNIT = "%"


def read(records, trace, cell):
    return olmo_work.roofline_pct(
        cell, trace, records, "gdn_scan",
        lambda cfg, block, tokens, **_: olmo_work.gdn_scan_work(
            cfg, block, tokens))
