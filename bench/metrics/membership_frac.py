"""Device time of the membership kernel (``kernels/membership``, which
``kernels/intersect`` calls) over the traced window, from the profiler
trace: the Mosaic custom calls of the ``membership`` and ``intersect``
jits.  Where the trace holds none the metric is left out of the line;
in a cell that lists it, that line is then refused."""


def is_membership(hlo: str) -> bool:
    return ('custom_call_target="tpu_custom_call"' in hlo
            and ("membership" in hlo or "intersect" in hlo))


def read(run):
    if run.trace is None:
        return None              # off the chip: no device plane
    t = run.trace.kernel_seconds(is_membership)
    return t / run.trace.window_s if t > 0 else None
