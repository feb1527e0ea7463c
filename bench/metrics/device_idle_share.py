"""device_idle_share (%): 1 minus the device's busy time over the traced
window, from the profiler trace (``bench/devtrace.py``)."""


def read(record):
    t = record["trace"]
    if t is None or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
