"""The 192 / 128 latent-attention core's share of its roofline in the
traced pass: the least time the chip could take for the causal ``q k^T``
at the key width 192 and ``a v`` at the value width 128 of every mixer,
unpadded, forward, and backward where the gradient reaches
(``xing_work.mla_core_work``), over the device time of the ``mla_core``
scope.  Padding the keys shows as a lower share, not as more work."""

from benchmarks.lib import xing_work

UNIT = "%"


def read(records, trace, cell):
    return xing_work.roofline_pct(
        cell, trace, records, lambda o: o.scope == "mla_core",
        lambda cfg, block, tokens, seq_len, **_: xing_work.mla_core_work(
            cfg, block, tokens, seq_len))
