"""The window's throughput with the obs recorder on (the profiled pass
left out).  Its distance from the untraced ``samples_per_s_chip`` is what
telemetry costs."""

UNIT = "samples/s/chip"


def read(records, trace, cell):
    return records.counters.get("traced_sps_chip")
