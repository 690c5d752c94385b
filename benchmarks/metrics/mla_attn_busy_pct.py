"""Share of the device's busy time in the traced pass spent in ops of the
program's ``mla_attn`` scope, the whole latent-attention mixer:
projections, norms, rotary and the core (``mla_core`` lies inside it;
``benchmarks/lib/glm_work.py``)."""

from benchmarks.lib import glm_work

UNIT = "%"


def read(records, trace, cell):
    return glm_work.busy_share_pct(
        cell, trace, lambda o: o.scope in glm_work.MIXER)
