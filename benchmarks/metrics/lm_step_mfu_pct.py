"""Model FLOP/s utilisation of the window's rounds outside the profiled
pass: the operations the visited blocks' forward and backward passes
need (``lm_work.round_flops``: weight gradients for the active block
only, nothing recomputed) over the rounds' seconds, the chips and the
chip's bfloat16 peak."""

from benchmarks.lib import lm_work, peaks

UNIT = "%"


def read(records, trace, cell):
    rounds = [r for r in records.rounds(traced=False) if "tokens" in r]
    seconds = sum(r["round_seconds"] for r in rounds)
    if not rounds or seconds <= 0 or trace is None:
        return None
    flops = sum(lm_work.round_flops(
        cell.config, seq_len=int(cell.config["seq_len"]),
        **lm_work.round_of(cell, r)) for r in rounds)
    peak = peaks.peaks_for(trace.device_kind)["bf16_flops"]
    return 100.0 * flops / seconds / records.chips / peak
