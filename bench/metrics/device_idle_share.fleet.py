"""The device's idle share of the traced window: 1 - (union of the
device-op intervals) / window, from the profiler trace."""


def read(run):
    s = run.trace_summary
    if s is None or s["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])
