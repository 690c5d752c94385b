"""Model FLOP/s utilisation of the ``olmo_hybrid`` cell's rounds outside
the profiled pass: the operations the visited blocks' forward and backward
passes need (``olmo_work.round_flops``: three Gated DeltaNet mixers with
their recurrences, the attention mixer, four MLPs and the head forward;
the backward of what the gradient reaches; the active block's weight
gradients only; the causal half of attention; nothing recomputed) over
the rounds' seconds, the chips and the chip's bfloat16 peak.  The whole
step's share."""

from benchmarks.lib import olmo_work, peaks

UNIT = "%"


def read(records, trace, cell):
    rounds = [r for r in records.rounds(traced=False) if "tokens" in r]
    seconds = sum(r["round_seconds"] for r in rounds)
    if not rounds or seconds <= 0 or trace is None:
        return None
    flops = sum(olmo_work.round_flops(cell.config,
                                      **olmo_work.round_of(cell, r))
                for r in rounds)
    peak = peaks.peaks_for(trace.device_kind)["bf16_flops"]
    return 100.0 * flops / seconds / records.chips / peak
