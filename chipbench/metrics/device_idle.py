"""device_idle: share of the traced window in which no operation ran on the
device, 100 x (1 - busy / window), from the profiler trace. None without
a trace or without a device operation in it."""


def read(run):
    t = run.device_trace
    if t is None or t["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
