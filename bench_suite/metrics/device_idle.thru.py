"""1 - (union of device-op intervals / traced window), from the profiler
trace; the throughput cells' reading."""


def read(run):
    return run.trace.idle_share if run.trace is not None else None
