"""The hyper-connections' share of their roofline in the traced pass: the
least time the chip could take for their work (``xing_work.mhc_work``:
the streams read once and written once a sub-layer and pass, the
projections' products; the larger of operations / peak and bytes /
bandwidth) over the device time of the ops through the ``mhc`` scope."""

from benchmarks.lib import xing_work

UNIT = "%"


def read(records, trace, cell):
    return xing_work.roofline_pct(
        cell, trace, records, lambda o: o.mhc,
        lambda cfg, block, tokens, **_: xing_work.mhc_work(cfg, block,
                                                           tokens))
