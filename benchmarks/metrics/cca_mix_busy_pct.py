"""Share of the device's busy time in the traced pass spent in what
compressing attention adds around the core: the ``cca_mix`` scope (the
value shift, both causal convolutions, the means) and ``attn_norm_rope``
(the unit-sphere norm, the key temperature, rotary) where they hang
under ``cca_attn``, worst chip (``benchmarks/lib/zaya_work.py``)."""

from benchmarks.lib import zaya_work

UNIT = "%"


def read(records, trace, cell):
    return zaya_work.busy_share_pct(
        cell, trace, lambda tree: zaya_work.seconds_under(
            tree, "cca_attn", ("cca_mix", "attn_norm_rope")))
