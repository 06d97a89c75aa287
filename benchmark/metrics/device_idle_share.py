"""device_idle_share: 1 - (union of device-op intervals / traced
window), from the profiler trace, averaged over the chips. Nothing to
read where the trace holds no device plane."""


def read(run):
    if run.trace is None or run.trace.chips == 0 or run.trace.window_ns <= 0:
        return None
    return 1.0 - run.trace.busy_ns / run.trace.window_ns
