"""The latent-attention core's share of its roofline in the traced pass:
the least time the chip could take for the causal ``q k^T`` and ``a v``
of every mixer, forward, and backward where the gradient reaches
(``glm_work.mla_core_work``), over the device time of the ``mla_core``
scope."""

from benchmarks.lib import glm_work

UNIT = "%"


def read(records, trace, cell):
    return glm_work.roofline_pct(
        cell, trace, records, "mla_core",
        lambda cfg, block, tokens, seq_len, **_: glm_work.mla_core_work(
            cfg, block, tokens, seq_len))
