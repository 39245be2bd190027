"""unscoped_ms_per_lane_cycle: device time of the window executable's ops
under no `cycle.*` scope (the key chain, the loop and cond scaffolding),
over the lane-cycles of the traced windows.  With the five phases it adds
up to the executable's op time.  Layer: cycle step.  Moves
lane_cycles_per_s."""
from bench import program


def read(run, trace):
    return program.ms_per_lane_cycle(program.UNSCOPED, run, trace)
