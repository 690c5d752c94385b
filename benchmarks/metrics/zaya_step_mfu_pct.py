"""Model FLOP/s utilisation of the ``zaya`` cell's rounds outside the
profiled pass: the operations the visited blocks' forward and backward
passes need (``zaya_work.round_flops``: forward of every part, backward
of what the gradient reaches, weight gradients for the active block
only, nothing recomputed, the causal half of the core, the held share of
the experts by the rounds' ``moe_pairs_local``) over the rounds' seconds,
the chips and the chip's bfloat16 peak.  The whole step's share."""

from benchmarks.lib import peaks, zaya_work

UNIT = "%"


def read(records, trace, cell):
    rounds = [r for r in records.rounds(traced=False) if "tokens" in r]
    seconds = sum(r["round_seconds"] for r in rounds)
    if not rounds or seconds <= 0 or trace is None:
        return None
    flops = sum(zaya_work.round_flops(cell.config,
                                      **zaya_work.round_of(cell, r))
                for r in rounds)
    peak = peaks.peaks_for(trace.device_kind)["bf16_flops"]
    return 100.0 * flops / seconds / records.chips / peak
