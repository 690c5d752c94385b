"""Dispatches of the window that met a new argument signature: the sum
of the round field ``dispatch_new_signatures`` (``obs/costs.py``: a call
after which the jitted site's cache grew while nothing was retraced)
over the window's rounds, the profiled pass included.  The field is
omitted at 0; a program that writes ``dispatch_max_seconds`` beside
``dispatch_seconds`` counts, so absent reads 0 there and None on a
program without the counter.  The first reading prints every round of
the run that met one, the untimed pass's too, as ``new_signatures=[[pass
(-1: untimed), round in the pass, site of the slowest call, its ms,
count], ...]``."""

import json

UNIT = "count"


def read(records, trace, cell):
    rounds = [r for r in records.rounds() if "dispatch_max_seconds" in r]
    if not rounds:
        return None
    if not getattr(records, "_new_signatures_printed", False):
        records._new_signatures_printed = True
        passes = [(-1, records.warmup or [])] + [
            (i, p.records) for i, p in enumerate(records.passes)]
        print("new_signatures=" + json.dumps([
            [i, j, r.get("dispatch_max_site", ""),
             round(1e3 * r.get("dispatch_max_seconds", 0.0), 3),
             r["dispatch_new_signatures"]]
            for i, recs in passes for j, r in enumerate(recs)
            if r.get("dispatch_new_signatures")]))
    return sum(r.get("dispatch_new_signatures", 0) for r in rounds)
