"""device_ms_per_lane_cycle: device busy time in the traced windows (the
union of each device's op intervals), summed over the devices that ran
lanes, over the lane-cycles those windows simulated: device-milliseconds
per lane-cycle, whatever the number of chips, to which the six phase
readers add up.  Layer: cycle step.  Moves lane_cycles_per_s."""


def read(run, trace):
    if not trace or not run.get("lane_cycles_traced") or trace["busy_s"] <= 0:
        return None
    return 1e3 * trace["busy_sum_s"] / run["lane_cycles_traced"]
