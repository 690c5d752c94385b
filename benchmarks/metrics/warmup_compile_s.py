"""Seconds of compilation (or of loading from the compile cache) in the
untimed pass: sum of ``compile_seconds`` over its round records."""

UNIT = "s"


def read(records, trace, cell):
    return float(sum(r.get("compile_seconds", 0.0) for r in records.warmup))
