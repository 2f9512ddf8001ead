"""Share of the traced window in which no operation ran on the device
(1 - union of op intervals / window), averaged over the chips."""


def read(v):
    if not v.trace.devices:
        return None
    return 100.0 * (1.0 - v.trace.mean_busy_ns() / v.trace.window_ns)
