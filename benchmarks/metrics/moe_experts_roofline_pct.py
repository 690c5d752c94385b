"""The held experts' share of their roofline in the traced pass: the
least time the chip could take for the token-expert pairs' products with
the held weights read once (``lm_work.moe_experts_work``) over the device
time of the ``moe_experts`` scope."""

from benchmarks.lib import lm_work, scopes

UNIT = "%"


def read(records, trace, cell):
    return scopes.roofline_pct(
        cell, trace, records, "moe_experts",
        lambda cfg, r: lm_work.moe_experts_work(cfg, r["block"],
                                                r["pairs_local"]))
