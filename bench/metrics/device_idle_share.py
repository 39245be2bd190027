"""device_idle_share: the share of the traced window in which no op ran
on a device, in percent (1 - busy / window), the mean over the devices
that ran lanes.  Layer: device.  Moves lane_cycles_per_s."""


def read(run, trace):
    if not trace:
        return None
    lo, hi = trace["window"]
    if hi <= lo or trace["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / (hi - lo))
