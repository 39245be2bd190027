"""inject_ms_per_lane_cycle: device time of the window executable's ops
that the program scopes `cycle.inject` (packet generation and the
source-queue push), over the lane-cycles of the traced windows. Layer:
cycle step. Moves lane_cycles_per_s."""
from bench import program


def read(run, trace):
    return program.ms_per_lane_cycle("inject", run, trace)
