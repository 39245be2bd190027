"""route_ms_per_lane_cycle: device time of the window executable's ops that
the program scopes `cycle.route` (request rows: head gathers, route
lookup, VC expansion, route-at-push), over the lane-cycles of the traced
windows. Layer: cycle step. Moves lane_cycles_per_s."""
from bench import program


def read(run, trace):
    return program.ms_per_lane_cycle("route", run, trace)
