"""device_idle_share: the share of the window in which no operation ran on
the device, from the profiler's trace: 1 - (union of device-op intervals) /
window (%)."""


def read(ctx):
    trace = ctx["trace"]
    if trace.busy_s <= 0:
        return None
    return 100.0 * (1.0 - trace.busy_s / trace.window_s)
