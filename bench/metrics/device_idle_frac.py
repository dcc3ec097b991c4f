"""1 - (union of the device's operation intervals / traced window), from
the profiler trace."""


def read(run):
    return run.trace.idle_frac if run.trace is not None else None
