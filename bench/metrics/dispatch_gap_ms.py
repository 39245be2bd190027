"""dispatch_gap_ms: mean device-idle time between one window's execution
and the next one's, in the traced windows (the host's block, loop and
dispatch between windows).  Layer: dispatch.  Moves lane_cycles_per_s."""


def read(run, trace):
    if not trace or not trace["module_gaps"]:
        return None
    gaps = trace["module_gaps"]
    return 1e3 * sum(gaps) / len(gaps)
