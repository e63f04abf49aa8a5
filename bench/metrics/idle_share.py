"""Share of the traced window in which no op ran on the device, in %,
averaged over the chips used: 1 - (union of busy intervals) / window.
Layer: device."""


def read(run):
    red = run.reduction
    if red is None or not red.busy_s or red.window_s <= 0:
        return None
    return 100.0 * (1.0 - red.mean_busy_s() / red.window_s)
