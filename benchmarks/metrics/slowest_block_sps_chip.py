"""Throughput of the schedule's slowest block: the least, over blocks,
of samples per round / median ``round_seconds`` of that block's rounds in
the window / chips (ROADMAP S5: the stem)."""

import statistics

UNIT = "samples/s/chip"


def read(records, trace, cell):
    by_block = {}
    for r in records.rounds(traced=False):
        by_block.setdefault((r.get("model"), r["block"]), []).append(
            r["round_seconds"])
    if not by_block:
        return None
    slowest = max(statistics.median(v) for v in by_block.values())
    return records.samples_per_round / slowest / records.chips
