"""The compressed attention core's share of its roofline in the traced
pass: the least time the chip could take for the causal ``q k^T`` and
``a v`` at 8 query heads on 2 key/value heads of 128 as the ``cca_core``
scope runs them (``zaya_work.cca_core_work``: forward in every mixer,
once more and backward where the gradient reaches; the larger of
operations / peak and bytes / bandwidth) over that scope's device
time."""

from benchmarks.lib import zaya_work

UNIT = "%"


def read(records, trace, cell):
    return zaya_work.roofline_pct(
        cell, trace, records, "cca_core",
        lambda cfg, block, tokens, seq_len, **_: zaya_work.cca_core_work(
            cfg, block, tokens, seq_len))
